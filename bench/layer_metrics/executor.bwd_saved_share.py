"""The share of the last stage's backwards that consumed their forward's
residuals instead of running the forward again: of the traced
``repro.exec.run_bwd`` spans of the last stage, the percentage that hold
a ``repro.exec.bwd_saved`` span on their own thread.  A trace with no
``repro.exec.bwd_saved`` span (a program without the saved backward)
reads nothing."""
from bench import program_spans

BWD, SAVED = "repro.exec.run_bwd", "repro.exec.bwd_saved"


def read(r):
    spans = program_spans.of(r)
    saved = [s for s in spans if s.name == SAVED]
    last = r.config.get("n_stages", 0) - 1
    bwds = [s for s in spans
            if s.name == BWD and s.stats.get("stage") == last]
    if not saved or not bwds:
        return None
    hits = sum(any(s.line == b.line and b.start_ns <= s.start_ns
                   and s.end_ns <= b.end_ns for s in saved) for b in bwds)
    return 100.0 * hits / len(bwds)
