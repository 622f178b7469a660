"""Host self time of ``search.run`` per search (ms): the search loop's
own work, its simulator calls left out, from the program's own spans
(``repro.obs.host_summary``)."""
from harness.spans import self_ms


def read(run):
    return self_ms(run, "search.run".__eq__, len(run.units))
