"""Mean share of the decode batch's slots that SchedulerCore filled: rows
passed to JaxBackend.decode over max_slots, over the window's steps, %."""


def read(run):
    v = [s.rows for s in run.rec.decodes if run.open <= s.t0 < run.close]
    return 100.0 * sum(v) / (len(v) * run.rec.max_slots) if v else None
