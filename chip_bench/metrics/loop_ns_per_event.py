"""Serving loop (scheduler, allocator, lowering, fleet event loop, pricing):
host ns per simulated event, from the sweep's own ``timing["loop_s"]``."""


def read(record: dict):
    if not record.get("work") or "loop_s" not in record:
        return None
    return record["loop_s"] / record["work"] * 1e9
