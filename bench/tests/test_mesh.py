"""A traffic with a `mesh`: the program's sharded step over the cell's
devices, the reference laid out over the same devices, and the one-device
path left as it was."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import calibrate, compare, run
from bench.drivers import train
from bench.reference import lm
from bench.tests import tiny


@pytest.fixture(scope="module")
def mesh_run():
    """`bench/tests/mesh_run.py` on four host devices, in a process of its
    own: the test run's processes keep one."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([run.ROOT,
                                           os.path.join(run.ROOT, "src")]))
    done = subprocess.run([sys.executable, "-m", "bench.tests.mesh_run"],
                          cwd=run.ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_a_mesh_traffic_runs_the_sharded_step_over_four_devices(mesh_run):
    sound = mesh_run["sound"]
    assert sound["correct"] is True, sound["checks"]
    assert sound["device"]["count"] == 4
    assert sound["failed"] == 0 and sound["attempted"] >= 1
    assert mesh_run["state_devices"] == 4
    assert mesh_run["leaves_split"] > 0


def test_the_mesh_reads_what_one_device_reads(mesh_run):
    gaps = mesh_run["mesh_vs_one_device"]
    assert run.judge(gaps, tiny.LIMITS["tiny-dense"]), gaps


def test_the_half_batch_fault_fails_on_the_mesh(mesh_run):
    result = mesh_run["half_batch"]
    assert result["correct"] is False
    assert any(not c["value"] <= c["limit"]
               for c in result["checks"].values()), result["checks"]


def test_the_float8_control_fails_on_the_mesh(mesh_run):
    assert not run.judge(mesh_run["control"], tiny.LIMITS["tiny-dense"])


def _tree(tmp_path, chips, mesh):
    """A benchmark of one cell in `tmp_path`, as `cell_spec` reads it."""
    def write(*parts, data):
        path = tmp_path.joinpath(*parts)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(data))
    write("BENCHMARK.json", data={
        "workloads": [{"name": "tiny.train", "config": "tiny",
                       "traffic": "mesh", "chips": chips}],
        "end_to_end": tiny.END_TO_END, "per_layer": tiny.PER_LAYER})
    write("bench", "configs", "tiny.json", data=tiny.DENSE)
    write("bench", "traffic", "mesh.json",
          data=dict(tiny.TRAFFIC["tiny-dense"], mesh=mesh))
    write("bench", "limits", "tiny.train.json",
          data=tiny.LIMITS["tiny-dense"])
    return str(tmp_path), str(tmp_path / "bench")


@pytest.mark.parametrize("chips,mesh", [(4, {"data": 2, "model": 2}),
                                        (4, {"data": 4, "model": 1}),
                                        (2, {"data": 1, "model": 2})])
def test_cell_spec_takes_a_mesh_of_the_cells_size(tmp_path, monkeypatch,
                                                  chips, mesh):
    root, bench = _tree(tmp_path, chips, mesh)
    monkeypatch.setattr(run, "ROOT", root)
    monkeypatch.setattr(run, "BENCH", bench)
    assert run.cell_spec("tiny.train")["traffic"]["mesh"] == mesh


@pytest.mark.parametrize("chips,mesh", [(1, {"data": 2, "model": 2}),
                                        (4, {"data": 2, "model": 1}),
                                        (2, {"data": 2, "model": 2})])
def test_cell_spec_refuses_a_mesh_of_another_size(tmp_path, monkeypatch,
                                                  chips, mesh):
    root, bench = _tree(tmp_path, chips, mesh)
    monkeypatch.setattr(run, "ROOT", root)
    monkeypatch.setattr(run, "BENCH", bench)
    with pytest.raises(ValueError, match="mesh"):
        run.cell_spec("tiny.train")


def _batch(rows, positions):
    tokens = jnp.arange(rows * positions, dtype=jnp.int32).reshape(
        rows, positions)
    return {"tokens": tokens, "labels": tokens + 1}


@pytest.mark.parametrize("rows,data,shape", [(2, 2, (2, 32)),
                                             (4, 2, (2, 64)),
                                             (8, 4, (4, 64)),
                                             (4, 4, (4, 32)),
                                             (1, 1, (1, 32)),
                                             (4, 1, (2, 64))])
def test_half_keeps_the_rows_left_divisible_over_data(rows, data, shape):
    batch = _batch(rows, 64)
    halved = calibrate.half(batch, data)
    assert halved["tokens"].shape == halved["labels"].shape == shape
    assert shape[0] % data == 0
    np.testing.assert_array_equal(
        halved["tokens"], batch["tokens"][:shape[0], :shape[1]])


TRAFFICS = sorted(f[:-len(".json")] for f in os.listdir(
    os.path.join(run.BENCH, "traffic")) if f.endswith(".json"))


@pytest.mark.parametrize("traffic", TRAFFICS)
def test_half_gives_the_committed_traffic_what_it_gave_before(traffic):
    spec = run.load_json(run.BENCH, "traffic", traffic + ".json")
    batch = _batch(spec["batch"], 16)
    # Before meshes: half of the rows, or of the positions of a single row.
    expect = (batch["tokens"][:spec["batch"] // 2] if spec["batch"] > 1
              else batch["tokens"][:, :8])
    data = spec.get("mesh", {}).get("data", 1)
    np.testing.assert_array_equal(calibrate.half(batch, data)["tokens"],
                                  expect)


def _abstract(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


def test_the_one_device_step_lowers_as_before_with_devices_passed():
    """A traffic without a mesh gets no layout, and its step, initialiser,
    batches and change program lower to the text they lowered to before
    the mesh path (the jit calls written as they were)."""
    from repro.optim import AdamWConfig
    from repro.runtime.steps import (TrainOptions, init_train_state,
                                     make_train_step)
    arch = train.arch_config(tiny.DENSE)
    traffic = tiny.TRAFFIC["tiny-dense"]
    lay = train.layout(arch, traffic, jax.devices()[:1])
    assert lay is None
    key = train.seed_key(3)
    state = jax.eval_shape(lambda: init_train_state(key, arch))
    batch = {k: jax.ShapeDtypeStruct((traffic["batch"], traffic["seq_len"]),
                                     jnp.int32) for k in ("tokens", "labels")}
    before = jax.jit(make_train_step(
        arch, AdamWConfig(), TrainOptions(remat="group",
                                          chunk=min(512, traffic["seq_len"]))),
        donate_argnums=(0,))
    texts = {fn.lower(state, batch).as_text()
             for fn in (before, train.build_step(arch, traffic),
                        train.build_step(arch, traffic, lay))}
    assert len(texts) == 1
    init_before = jax.jit(lambda key: init_train_state(key, arch))
    assert len({fn.lower(key).as_text() for fn in (
        init_before, train.build_init(arch), train.build_init(arch, lay))}) == 1
    master = _abstract(state["opt"]["master"])
    from repro.models import init_params
    change_before = jax.jit(lambda master, key: compare.norms(jax.tree.map(
        lambda m, p: m - p.astype(jnp.float32), master,
        init_params(key, arch))))
    assert len({fn.lower(master, key).as_text() for fn in (
        change_before, train.build_change(arch),
        train.build_change(arch, lay))}) == 1


@pytest.mark.parametrize("shape,spec", [((12, 8), ("devices", None)),
                                        ((6, 8), (None, "devices")),
                                        ((6, 4, 16), (None, None, "devices")),
                                        ((3, 5), (None, None)),
                                        ((), ())])
def test_the_reference_splits_each_array_along_its_largest_even_axis(
        shape, spec):
    mesh = jax.sharding.AbstractMesh((4,), ("devices",))
    assert tuple(lm.spread(shape, mesh).spec) == spec
