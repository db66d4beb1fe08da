"""The readers of device time in collectives (`bench/collectives.py`,
`bench/metrics/*.train_mesh.py`): on a trace recorded over four host
devices, on the one-device trace, and on instruction texts shaped like a
TPU trace's event names."""
import os

import pytest

from bench import collectives, flops, run, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
MESH_NAMES = {"all-gather", "all-gather.1", "all-reduce", "all-to-all.1",
              "collective-permute", "collective-permute.1",
              "collective-permute.2", "collective-permute.3"}


def _context(fixture):
    return {"trace": trace.reduce_file(os.path.join(DATA, fixture)),
            "window_s": 0.5, "tokens": 4096, "chips": 4,
            "config": run.load_json(run.BENCH, "configs", "glm4-9b.json"),
            "peaks": run.device_peaks("TPU v5 lite"),
            "flops": flops}


@pytest.fixture(scope="module")
def mesh():
    return _context("cpu_mesh_window.xplane.pb")


def test_the_collectives_of_the_four_device_trace_are_found(mesh):
    ops = mesh["trace"]["ops"]
    found = {name for name in ops if collectives.is_collective(name)}
    # The fixture's compiled program holds exactly these (its script).
    assert found == MESH_NAMES
    assert collectives.seconds(mesh["trace"]) == pytest.approx(
        sum(ops[n] for n in MESH_NAMES))


def test_the_collective_metrics_read_the_four_device_trace(mesh):
    s = collectives.seconds(mesh["trace"])
    t = mesh["trace"]
    assert s > 0
    assert run.metric_reader("collective_us.train_mesh")(mesh) == \
        pytest.approx(1e6 * s / 4096)
    assert run.metric_reader("collective_share.train_mesh")(mesh) == \
        pytest.approx(100.0 * s / (t["busy_s"] * t["devices"]))


@pytest.mark.parametrize("metric", ["collective_us.train_mesh",
                                    "collective_share.train_mesh"])
def test_the_collective_metrics_find_nothing_on_one_device(metric):
    assert run.metric_reader(metric)(_context("cpu_window.xplane.pb")) is None


@pytest.mark.parametrize("fixture", ["cpu_mesh_window.xplane.pb",
                                     "cpu_window.xplane.pb"])
@pytest.mark.parametrize("name", ["mfu.train", "idle_share.train"])
def test_the_mesh_entries_read_what_the_accepted_readers_read(name, fixture):
    context = _context(fixture)
    expect = run.metric_reader(name)(context)
    assert expect is not None
    assert run.metric_reader(name + "_mesh")(context) == expect


@pytest.mark.parametrize("event,expect", [
    ("%all-gather-start.3 = (bf16[4096]{0}, bf16[8192]{0}) "
     "all-gather-start(bf16[4096]{0} %p.1), dimensions={0}", True),
    ("%all-gather-done.3 = bf16[8192]{0} all-gather-done(%all-gather-start.3)",
     True),
    ("%ar.1 = f32[8]{0:T(256)} all-reduce(f32[8]{0} %x), to_apply=%add", True),
    ("%reduce-scatter.2 = f32[4]{0} reduce-scatter(%y), dimensions={0}",
     True),
    ("%all-reduce-scatter-fusion.1 = bf16[64]{0} fusion(%a), kind=kOutput",
     True),
    ("%fusion.9 = bf16[64]{0} fusion(%a), kind=kCustom, "
     "calls=%all-gather-fusion.4", True),
    ("%collective-permute-start.2 = (f32[8], f32[8]) "
     "collective-permute-start(%z), source_target_pairs={{0,1}}", True),
    ("%all-to-all.1 = f32[8]{0} all-to-all(%w), dimensions={0}", True),
    ("%fusion.7 = bf16[4,512]{1,0} fusion(%p.2, %p.3), kind=kLoop, "
     "calls=%fused_computation.7", False),
    ("%reduce.5 = f32[] reduce(f32[8]{0} %x, f32[] %c), to_apply=%add",
     False),
    ("%convolution.3 = bf16[4,64]{1,0} convolution(%a, %b), "
     "dim_labels=bf_io->bf", False),
    ("%copy-start.1 = (f32[8], f32[8], u32[]) copy-start(%x)", False),
    ("dot.1", False),
    ("all-reduce.4", True),
])
def test_an_event_is_collective_by_name_opcode_or_called_fusion(event,
                                                                 expect):
    assert collectives.is_collective(event) is expect
