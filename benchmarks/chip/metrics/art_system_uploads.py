"""Copies of an ART system matrix to the device inside the window: the
program's ``tomo_system_placements_total`` counter, read around each of the
window's micro-batches. The operator places the matrix once, in set-up, so
this reads 0."""


def read(run):
    return run.facts.get("system_uploads")
