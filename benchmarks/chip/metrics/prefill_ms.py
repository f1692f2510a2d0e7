"""Mean host-clock time of one JaxBackend.start (a whole prefill, ending in
the host read of the first token) over the window's prefills, ms."""


def read(run):
    v = [s.t1 - s.t0 for s in run.rec.prefills if run.open <= s.t0 < run.close]
    return 1e3 * sum(v) / len(v) if v else None
