"""Seconds of set-up spent in the first calls of the partials program: the
program's `batch.first_call/<flavour>` spans whose flavour is a partials
program (`g1_partials_*` or `g2_partials_*`; tracing, lowering, compiling
or loading it from the cache), in the verify service's `stats()["spans"]`
snapshot taken when set-up ends; nothing where there is none."""

PREFIX = "batch.first_call/"


def read(rec):
    spans = rec["stats0"].get("spans")
    if not spans:
        return None
    secs = [v[1] for k, v in spans.items() if k.startswith(PREFIX)
            and "/" not in k[len(PREFIX):] and "_partials_" in k]
    return sum(secs) if secs else None
