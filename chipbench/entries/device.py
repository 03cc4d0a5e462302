"""Frames already on the device, through ``CompiledApp.launch`` and
``LaunchHandle.result``: one frame a call, the frame sets left where the
seed made them.  Set-up warms the app's one jitted call."""
from __future__ import annotations

from harness import drive


def start(cell, app, pool_dev: list[dict], devices: list) -> drive.Entry:
    import jax
    jax.block_until_ready(app.launch(**pool_dev[0]).outputs)
    return drive.Entry(pool=pool_dev,
                       issue=lambda inputs: app.launch(**inputs),
                       wait=lambda handle, timeout: handle.result())


def programs(cell, app, plane, devices: list) -> list:
    """``(label, compile)`` for each program the window runs, with
    ``app`` compiled unjitted and ``plane`` an input's shape."""
    import jax
    return [("app call", lambda: jax.jit(app.fn).lower(
        *[plane] * len(app.input_names)).compile())]
