"""interstage_mb (MB, program counter): HBM megabytes one frame moves
from one kernel group to another (core/schedule.py
Schedule.interstage_bytes: each plane written once and read by each
group that reads it).  After the window the schedule is rebuilt from
the cell's configuration through build_app and build_schedule, as
compile_graph builds it; None from a program without the counter."""


def read(run):
    from repro.core.apps import build_app
    from repro.core.schedule import build_schedule

    cfg = run.cell.config
    graph = build_app(cfg["app"], int(cfg["height"]), int(cfg["width"]))
    sched = build_schedule(graph, backend="pallas")
    count = getattr(sched, "interstage_bytes", None)
    return None if count is None else count() / 1e6
