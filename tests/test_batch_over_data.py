"""`parallel.sharding.batch_over_data`: the layer activations' batch axis
kept sharded over the mesh's data axes.  On a 2x2 mesh of host devices, in
a process of its own (the test run's processes keep one host device), the
sharded step equals the one-device step, and the helper leaves its operand
untouched, lowering as the identity does, where it has nothing to shard."""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.parallel.sharding import batch_over_data

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The sharded step against the one-device step, as the sharded tests hold
# them: in bf16, `bench/tests/glm4_tiny.LIMITS` for the tiny GLM-4 cell's
# 2x2 run (loss, global gradient norm, and the worst leaf's gradient norm,
# each a relative gap; a leaf's over the larger of its norm and the median
# leaf's); in float32, elementwise, `bench/tests/test_glm4.py`'s
# LOSS_RTOL and GRAD_RTOL (the leaf gap is then the norm of the leaf's
# difference, which a misplaced shard cannot pass).
LIMITS = {"bfloat16": {"loss_gap": 3.2e-5, "grad_norm_gap": 3.5e-4,
                       "grad_leaf_gap": 1.1e-2},
          "float32": {"loss_gap": 1e-5, "grad_norm_gap": 1e-4,
                      "grad_leaf_gap": 1e-4}}

PROGRAM = """
import dataclasses, json, re
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, smoke_config
from repro.launch.mesh import make_host_mesh
from repro.launch.train import train_shardings
from repro.models import loss_fn
from repro.parallel.sharding import batch_over_data
from repro.runtime.steps import init_train_state

def text(lowered):
    return re.sub(r"^module @\\S+", "module", lowered.as_text(debug_info=False))

def leaves(tree):
    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]
    return {jax.tree_util.keystr(p): np.asarray(v, np.float32)
            for p, v in flat}

def norm(tree):
    return float(np.sqrt(sum(np.sum(v * v) for v in tree.values())))

mesh = make_host_mesh(2)
out = {}
for dtype in ("bfloat16", "float32"):
    cfg = dataclasses.replace(smoke_config(get_config("glm4-9b")),
                              dtype=dtype)
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 65), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    grad = jax.jit(jax.value_and_grad(
        lambda p, b: loss_fn(p, cfg, b, chunk=16)))
    state_sh, batch_sh = train_shardings(mesh, cfg, state)
    sharded = (jax.device_put(state["params"], state_sh["params"]),
               {k: jax.device_put(v, batch_sh[k]) for k, v in batch.items()})
    one = jax.device_put((state["params"], batch), jax.devices()[0])
    (loss2, g2), (loss1, g1) = grad(*sharded), grad(*one)
    a, b = leaves(g2), leaves(g1)
    norms = {k: float(np.linalg.norm(b[k])) for k in b}
    median = float(np.median(list(norms.values())))
    if dtype == "float32":
        leaf = {k: float(np.linalg.norm(a[k] - b[k])) for k in b}
    else:
        leaf = {k: abs(float(np.linalg.norm(a[k])) - norms[k]) for k in b}
    out[dtype] = {
        "loss_gap": abs(float(loss2) - float(loss1)) / abs(float(loss1)),
        "grad_norm_gap": abs(norm(a) - norm(b)) / norm(b),
        "grad_leaf_gap": max(leaf[k] / max(norms[k], median) for k in b),
        "constrained": '[{"data"}, {}, {}]' in text(grad.lower(*sharded))}

def identity(x):
    return x

def helper(x):
    return batch_over_data(x)

cases = {"batch_divides": ((4, 8, 16), mesh),
         "batch_does_not_divide": ((3, 8, 16), mesh),
         "one_data_replica": ((4, 8, 16), make_host_mesh(4))}
for name, (shape, m) in cases.items():
    x = jax.ShapeDtypeStruct(shape, jnp.float32,
                             sharding=NamedSharding(m, P()))
    seen = {}
    def probe(x):
        seen["untouched"] = batch_over_data(x) is x
        return x
    jax.jit(probe).lower(x)
    out[name] = {"untouched": seen["untouched"],
                 "same_lowering": text(jax.jit(helper).lower(x)) ==
                                  text(jax.jit(identity).lower(x))}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    done = subprocess.run([sys.executable, "-c", PROGRAM], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("dtype", sorted(LIMITS))
def test_sharded_step_constrains_the_layer_activations(readings, dtype):
    """The 2x2 step's layers hold their activations on `P("data", None,
    None)`: the helper engaged, so the comparison below is of it."""
    assert readings[dtype]["constrained"]


@pytest.mark.parametrize("dtype,gap", [(d, g) for d in sorted(LIMITS)
                                       for g in sorted(LIMITS[d])])
def test_sharded_step_equals_the_one_device_step(readings, dtype, gap):
    assert readings[dtype][gap] <= LIMITS[dtype][gap], readings[dtype]


@pytest.mark.parametrize("case,untouched", [("batch_divides", False),
                                            ("batch_does_not_divide", True),
                                            ("one_data_replica", True)])
def test_helper_engages_only_where_the_data_axes_divide_the_batch(
        readings, case, untouched):
    """On a mesh whose data axes hold more than one device and divide the
    batch the helper adds a constraint; otherwise it returns its operand,
    and the program lowers as the identity does."""
    assert readings[case] == {"untouched": untouched,
                              "same_lowering": untouched}, readings[case]


def _lowering(fn, x):
    return re.sub(r"^module @\S+", "module",
                  jax.jit(fn).lower(x).as_text(debug_info=False))


def test_helper_leaves_an_operand_off_a_mesh_untouched():
    seen = {}

    def probe(x):
        seen["untouched"] = batch_over_data(x) is x
        return x

    def helper(x):
        return batch_over_data(x)

    def identity(x):
        return x

    x = jax.ShapeDtypeStruct((4, 8, 16), jnp.bfloat16)
    jax.jit(probe).lower(x)
    assert seen["untouched"]
    assert _lowering(helper, x) == _lowering(identity, x)
