"""Scoring (trace build, coalescing, sort, replay, report): host ns per
simulated event, from the sweep's own ``timing["score_s"]``."""


def read(record: dict):
    if not record.get("work") or "score_s" not in record:
        return None
    return record["score_s"] / record["work"] * 1e9
