"""Seconds of set-up spent in the first calls of device programs: the
program's `batch.first_call` span (tracing, lowering, compiling or loading
each program flavour from the cache) in the verify service's
`stats()["spans"]` snapshot taken when set-up ends; nothing where the
program keeps no such span."""


def read(rec):
    spans = rec["stats0"].get("spans")
    if not spans or "batch.first_call" not in spans:
        return None
    return spans["batch.first_call"][1]
