"""Compiles of the index's jitted dispatches inside the window
(``obs/dispatch.py`` ``accounting_delta(...)["recompiles_by_site"]``,
summed over sites); warm-up should make it 0."""


def read(run):
    return run.record.get("window_recompiles")
