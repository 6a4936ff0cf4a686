"""Device idle share of the traced study, in percent: one minus the union of
device-op intervals over the traced window (profiler trace)."""


def read(record: dict):
    trace = record.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    return trace["idle_pct"]
