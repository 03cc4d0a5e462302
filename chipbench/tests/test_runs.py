"""Whole runs at a small size on the CPU: the harness past its look for
a chip, once sound and once for each fault a cell can have, with the
timed path broken underneath.  ``correct`` has to come out true for the
sound run and false for every fault, and for the control: the plain
reference in bfloat16 put in the program's place."""
import functools
import time
import types

import jax
import jax.numpy as jnp
import pytest

from harness import cells, drive, spec

SHAPE = {"height": 32, "width": 256}
SECONDS = 0.5


@pytest.fixture(autouse=True)
def few_frames(monkeypatch):
    monkeypatch.setattr(drive, "POOL", 3)
    monkeypatch.setattr(drive, "SAMPLE", 4)


def small(name: str) -> spec.Cell:
    cell = spec.load_cell(name)
    cell.config.update(SHAPE)
    if cell.traffic["loop"] == "open":
        cell.traffic.update(streams=2, stream_fps=20)
    else:
        cell.traffic["outstanding"] = min(cell.traffic["outstanding"], 8)
    return cell


def run(name: str) -> dict:
    cell = small(name)
    return cells.run(cell, 2 ** 33 + 17, SECONDS, False,
                     jax.devices()[:cell.chips], time.perf_counter())


CELLS = ["lk1080.cameras", "lk1080.device", "blur1080.device", "blur4k.x4"]
ENGINE_CELLS = ["lk1080.cameras", "blur4k.x4"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = run(name)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) >= {"fps", "setup_s"}
    assert list(out)[-1] == "checks"


def _alter_launch(monkeypatch):
    from repro.core import host
    orig = host.CompiledApp.launch

    def launch(self, **inputs):
        h = orig(self, **inputs)
        k = self.output_names[0]
        h.outputs[k] = h.outputs[k].at[3, 5].add(1.0)
        return h
    monkeypatch.setattr(host.CompiledApp, "launch", launch)


def _alter_batch(monkeypatch, rows):
    from repro.runtime import batching
    orig = batching.MicroBatcher.launch

    def launch(self, app, requests, *a, **kw):
        outs = orig(self, app, requests, *a, **kw)
        return {k: rows(v, len(requests)) for k, v in outs.items()}
    monkeypatch.setattr(batching.MicroBatcher, "launch", launch)


@pytest.mark.parametrize("name", ["lk1080.device", "blur1080.device"])
def test_an_answer_altered_where_the_kernel_gives_it_fails(name,
                                                           monkeypatch):
    _alter_launch(monkeypatch)
    assert not run(name)["correct"]


@pytest.mark.parametrize("name", ENGINE_CELLS)
def test_an_answer_altered_in_the_batch_fails(name, monkeypatch):
    _alter_batch(monkeypatch, lambda v, n: v.at[:n, 3, 5].add(1.0))
    assert not run(name)["correct"]


def test_half_of_the_batch_left_out_fails(monkeypatch):
    def half(v, n):
        return v.at[(n + 1) // 2:n].set(0.0) if n > 1 else v
    _alter_batch(monkeypatch, half)
    assert not run("blur4k.x4")["correct"]


@pytest.mark.parametrize("name", ENGINE_CELLS)
def test_a_frame_that_never_comes_fails(name, monkeypatch):
    from repro.runtime import engine
    real = spec.entry

    def entry(kind):
        mod = real(kind)

        def start(cell, app, pool_dev, devices):
            e = mod.start(cell, app, pool_dev, devices)
            issue, count = e.issue, [0]

            def dropping(inputs):
                count[0] += 1
                if count[0] == 3:            # accepted, never served
                    return engine.StreamRequest(app, inputs)
                return issue(inputs)
            e.issue = dropping
            return e
        return types.SimpleNamespace(start=start)
    monkeypatch.setattr(spec, "entry", entry)
    monkeypatch.setattr(drive, "GRACE_S", 0.5)
    out = run(name)
    assert not out["correct"] and out["failed"] == 1


@pytest.mark.parametrize("name", ["lk1080.device", "blur1080.device"])
def test_control_the_reference_in_bfloat16_fails(name, monkeypatch):
    from repro.core import host
    ref = spec.reference(small(name).config["app"])
    low = jax.jit(functools.partial(ref.reference, dtype=jnp.bfloat16))

    def launch(self, **inputs):
        return host.LaunchHandle({k: v.astype(jnp.float32)
                                  for k, v in low(inputs).items()})
    monkeypatch.setattr(host.CompiledApp, "launch", launch)
    assert not run(name)["correct"]
