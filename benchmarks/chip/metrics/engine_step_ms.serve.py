"""engine_step_ms.serve: mean host time of an engine step over the window,
from the engine's own counters (``EngineStats.wall / steps``).  Moves
itl_p95_ms."""


def read(run):
    c = run.counters
    if not c.get("engine_steps"):
        return None
    return 1e3 * c["engine_wall"] / c["engine_steps"]
