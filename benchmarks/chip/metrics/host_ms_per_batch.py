"""Host time per micro-batch outside the batch function: the pipeline's
own ``TraceLog`` span of each batch in the window, total minus its
``batch_fn`` stage (pump, sinks, commit, delivery), in ms."""


def read(run):
    spans = run.facts.get("batch_spans") or []
    if not spans:
        return None
    host = [s.total_s - s.stages.get("batch_fn", 0.0) for s in spans]
    return 1e3 * sum(host) / len(host)
