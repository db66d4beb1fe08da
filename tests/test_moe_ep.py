"""Expert-parallel MoE (`moe_impl="ep_shardmap"`) as the dry-run and the
hillclimb lower it: through `launch.dryrun.lower_cell`, on a 2x2 mesh of
host devices, in a process of its own (the test run's processes keep one
host device)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A reduced phi3.5-moe step, its experts dividing the mesh's "model" axis
# (4 on 2) and not dividing it (3 on 2); prints each step's count of
# all-to-alls in the lowered StableHLO.
PROGRAM = """
import dataclasses, json
from repro.configs import get_config, smoke_config
from repro.configs.base import ShapeConfig
from repro.launch.dryrun import lower_cell
from repro.launch.mesh import make_host_mesh
from repro.models.flags import flags

cfg = smoke_config(get_config("phi3.5-moe-42b-a6.6b"))
shape = ShapeConfig("train_tiny", 64, 4, "train")
counts = {}
for experts in (4, 3):
    with flags(moe_impl="ep_shardmap"):
        lowered, _, _ = lower_cell(dataclasses.replace(cfg, n_experts=experts),
                                   shape, make_host_mesh(model_parallel=2))
    counts[experts] = lowered.as_text().count("all_to_all")
print(json.dumps(counts))
"""


@pytest.fixture(scope="module")
def all_to_alls():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    done = subprocess.run([sys.executable, "-c", PROGRAM], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    counts = json.loads(done.stdout.strip().splitlines()[-1])
    return {int(k): v for k, v in counts.items()}


@pytest.mark.parametrize("experts,engaged", [(4, True), (3, False)])
def test_ep_engages_where_the_model_axis_divides_the_experts(
        all_to_alls, experts, engaged):
    """Where the mesh's "model" axis divides the experts, the step holds
    the shard_map's dispatch and return all-to-alls; where it does not,
    the layer falls back to the global `moe_forward` and holds none."""
    assert (all_to_alls[experts] > 0) == engaged, all_to_alls
