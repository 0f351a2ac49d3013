"""Scheduler: share of the lane-steps the engine fed that replayed a
prompt token whose logits were discarded, from the scheduler's own
counters (``lanes_replay``, ``lanes_emit`` in ``EngineLoop.stats()``)
as the harness read them when the window closed: the window's lanes and
the warm-up's few."""


def read(run):
    engine = run.engine_stats.get("engine", {})
    if "lanes_replay" not in engine:
        return None
    replay = engine["lanes_replay"]
    fed = replay + engine["lanes_emit"]
    return replay / fed if fed else None
