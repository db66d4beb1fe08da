"""GLM-4 in the program against its plain reference (`bench/reference/
glm4.py`): the equations on seeded random weights, a tiny cell through
`run.execute` on one device and over a 2x2 mesh of host devices, and the
faults the cell's comparison has to catch."""
import dataclasses
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
from bench.reference import glm4
from bench.tests import glm4_tiny

F32_CONFIG = dict(glm4_tiny.CONFIG, dtype="float32")
# The program in float32 against the reference at `highest`: on the CPU
# both multiply in float32 and agree to 2.4e-7 in loss and 2.1e-7 in the
# worst gradient leaf.  Full-head rotary, half-split pairs or a dropped
# bias move the loss by 8.0e-4 to 2.5e-3 and some leaf by 0.38 to 0.89.
LOSS_RTOL, GRAD_RTOL = 1e-5, 1e-4
VARIANTS = {"glm4": {},
            "full_head": {"partial_rotary_factor": 1.0},
            "half_split": {"rope_interleave": False},
            "no_bias": {"qkv_bias": False}}


def _random_params(key, arch):
    """The program's parameter tree with every leaf drawn from `key`: unit
    -scale embedding, matrices at 1/sqrt(fan-in), norm scales near 1 and
    biases of 0.5, so that rotary and bias move the attention scores."""
    from repro.models import init_params
    shapes = jax.eval_shape(lambda: init_params(key, arch))
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(flat))

    def draw(path, leaf, k):
        name = jax.tree_util.keystr(path)
        x = jax.random.normal(k, leaf.shape, leaf.dtype)
        if "ln" in name or "norm" in name:
            return 1.0 + 0.1 * x
        if "['b" in name:
            return 0.5 * x
        if "embed" in name:
            return x
        return x / np.sqrt(leaf.shape[-2])
    return jax.tree_util.tree_unflatten(
        tree, [draw(p, leaf, k) for (p, leaf), k in zip(flat, keys)])


def _grads(loss, params):
    """The loss and the norm of each leaf of its gradient."""
    value, grads = jax.value_and_grad(loss)(params)
    return float(value), compare.as_dict(compare.norms(grads))


def _agrees(variant: str) -> bool:
    """The program's loss and first gradient under `variant` against the
    reference's, both on the same seeded random weights and batch."""
    from repro.models import loss_fn
    ref_arch = train.arch_config(F32_CONFIG)
    arch = dataclasses.replace(ref_arch, **VARIANTS[variant])
    key = jax.random.PRNGKey(17)
    params = _random_params(key, ref_arch)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (2, 33), 0,
                                F32_CONFIG["vocab_size"])
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = _grads(
            lambda p: glm4.loss(p, batch["tokens"], batch["labels"],
                                F32_CONFIG), params)
    if not arch.qkv_bias:
        for group in params["groups"]:
            for b in ("bq", "bk", "bv"):
                del group["attn"][b]
    loss, grads = _grads(lambda p: loss_fn(p, arch, batch, chunk=16), params)
    if set(grads) != set(ref_grads):
        return False
    leaf_gap = max(abs(grads[k] - ref_grads[k]) / ref_grads[k]
                   for k in ref_grads)
    return abs(loss - ref_loss) / ref_loss <= LOSS_RTOL and \
        leaf_gap <= GRAD_RTOL


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_only_glms_rotary_and_bias_agree_with_the_reference(variant):
    assert _agrees(variant) == (variant == "glm4")


@pytest.mark.parametrize("rotary_dim,offset", [(8, 0), (16, 0), (8, 5)])
def test_the_reference_turns_adjacent_pairs_of_the_leading_dims(
        rotary_dim, offset):
    x = jax.random.normal(jax.random.PRNGKey(3), (6, 2, 16))
    pos = jnp.arange(6) + offset
    out = np.asarray(glm4._rope(x, pos, 1e4, rotary_dim))
    x = np.asarray(x)
    np.testing.assert_array_equal(out[..., rotary_dim:], x[..., rotary_dim:])
    for i in range(rotary_dim // 2):
        ang = np.asarray(pos, np.float32)[:, None] * 1e4 ** (-2 * i
                                                              / rotary_dim)
        c, s = np.cos(ang), np.sin(ang)
        a, b = x[..., 2 * i], x[..., 2 * i + 1]
        np.testing.assert_allclose(out[..., 2 * i], a * c - b * s,
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(out[..., 2 * i + 1], b * c + a * s,
                                   rtol=1e-5, atol=1e-5)


def test_the_reference_stops_a_program_without_the_rotary_fields(
        monkeypatch):
    from repro.configs import base

    @dataclasses.dataclass(frozen=True)
    class Before:
        name: str
        rope_theta: float = 1e4
    monkeypatch.setattr(base, "ArchConfig", Before)
    with pytest.raises(SystemExit, match="partial_rotary_factor"):
        glm4._check_program_states_the_rotary()


def test_a_tiny_glm4_cell_runs_correct_on_one_device():
    result = glm4_tiny.execute(glm4_tiny.spec(), jax.devices()[:1])
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1


def test_the_half_batch_fault_and_the_float8_control_fail_the_tiny_cell():
    cell = glm4_tiny.spec()
    seed = glm4_tiny.SEED
    prog, key, batches = calibrate.program_readings(cell, seed)
    ref = calibrate.reference_readings(cell, key, batches)
    control = calibrate.reference_readings(cell, key, batches, "fp8")
    half = calibrate.program_readings(cell, seed, calibrate.half)[0]
    assert run.judge(compare.gaps(prog, ref), cell["limits"])
    assert not run.judge(compare.gaps(control, ref), cell["limits"])
    assert not run.judge(compare.gaps(half, ref), cell["limits"])


@pytest.fixture(scope="module")
def mesh_run():
    """`bench/tests/glm4_tiny.py` on four host devices, in a process of its
    own: the test run's processes keep one."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([run.ROOT,
                                           os.path.join(run.ROOT, "src")]))
    done = subprocess.run([sys.executable, "-m", "bench.tests.glm4_tiny"],
                          cwd=run.ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_the_tiny_glm4_cell_runs_correct_over_a_2x2_mesh(mesh_run):
    sound = mesh_run["sound"]
    assert sound["correct"] is True, sound["checks"]
    assert sound["device"]["count"] == 4
    assert run.judge(mesh_run["mesh_vs_one_device"], glm4_tiny.LIMITS)


def test_the_faults_fail_the_tiny_glm4_cell_over_a_2x2_mesh(mesh_run):
    assert mesh_run["half_batch"]["correct"] is False
    assert not run.judge(mesh_run["control"], glm4_tiny.LIMITS)
