"""Model FLOP/s utilisation of the traced window, %: the model FLOPs of the
prompt tokens prefilled and the tokens decoded in it, at their true lengths
(counted by the arch module, work.model_flops), over the window times the
chip's bf16 peak."""
from benchmarks.chip import work


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    a, b = run.trace_host
    flops = sum(work.model_flops(run.config, s)
                for s in run.rec.prefills + run.rec.decodes
                if a <= s.t0 and s.t1 <= b)
    if flops <= 0:
        return None
    return 100.0 * flops / (t.window_s * run.peaks["bf16_flops"])
