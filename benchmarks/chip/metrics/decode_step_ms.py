"""Mean host-clock time of one JaxBackend.decode (one decode step for every
running request, ending in the host read of the tokens) in the window, ms."""


def read(run):
    v = [s.t1 - s.t0 for s in run.rec.decodes if run.open <= s.t0 < run.close]
    return 1e3 * sum(v) / len(v) if v else None
