"""The benchmark's own yardstick: its copy of the model-FLOP arithmetic, its
table of peaks, and the cells `BENCHMARK.json` names."""
import json
import os

import pytest

from bench import compare, flops, run

CELLS = {w["name"]: w for w in run.load_json(run.ROOT, "BENCHMARK.json")
         ["workloads"]}


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_flops_copy_equals_the_programs_arithmetic(workload):
    from repro.configs.base import ShapeConfig, model_flops
    from bench.drivers.train import arch_config
    spec = run.cell_spec(workload)
    cfg, traffic = spec["config"], spec["traffic"]
    tokens = traffic["batch"] * traffic["seq_len"]
    expect = model_flops(arch_config(cfg), ShapeConfig(
        "cell", traffic["seq_len"], traffic["batch"], "train"))
    assert flops.train_flops_per_token(cfg) * tokens == pytest.approx(
        expect, rel=1e-12)


def test_peaks_are_the_published_v5e_numbers_and_unknown_kinds_raise():
    v5e = run.device_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        run.device_peaks("TPU v9 imaginary")


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_every_cell_resolves_to_files_and_reports_what_the_contract_asks(
        workload):
    spec = run.cell_spec(workload)
    assert spec["traffic"]["kind"] == "train"
    assert spec["limits"] and set(spec["limits"]) <= {
        "loss_gap", "grad_norm_gap", "grad_leaf_gap", "update_leaf_gap"}
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        assert callable(run.metric_reader(m["name"]))
        assert m["moves"] in e2e
    assert os.path.exists(os.path.join(
        run.BENCH, "reference", spec["config"]["reference"] + ".py"))


def test_the_configuration_files_hold_the_published_widths():
    q = run.load_json(run.BENCH, "configs", "qwen2-0.5b.json")
    h = run.load_json(run.BENCH, "configs", "hymba-1.5b.json")
    assert (q["n_layers"], q["d_model"], q["n_heads"], q["n_kv_heads"],
            q["head_dim"], q["d_ff"], q["vocab_size"]) == \
        (24, 896, 14, 2, 64, 4864, 151936)
    assert (h["d_model"], h["n_heads"], h["n_kv_heads"], h["head_dim"],
            h["d_ff"], h["vocab_size"], h["ssm_state"], h["ssm_expand"],
            h["window"]) == (1600, 25, 5, 64, 5504, 32001, 16, 2, 1024)
    assert h["reduced"] == ["n_layers"] and h["published"]["n_layers"] == 32


def test_leaf_gap_is_measured_against_the_median_leaf_when_a_leaf_is_tiny():
    ref = {"a": 1.0, "b": 2.0, "c": 3.0, "tiny": 1e-9}
    prog = dict(ref, tiny=1e-3)
    # |1e-3 - 1e-9| against the median leaf's 1.5, not against 1e-9.
    assert compare.worst_leaf_gap(prog, ref) == pytest.approx(1e-3 / 1.5)
    assert compare.moving_leaves(ref) == ["a", "b", "c"]


def test_the_limit_rule_takes_the_least_reading_that_fails():
    from bench import calibrate
    summary = {"program": {"loss_gap": 1e-4, "grad_leaf_gap": 1e-2,
                           "grad_norm_gap": 1e-3},
               "control": {"loss_gap": 2e-4, "grad_leaf_gap": 0.5,
                           "grad_norm_gap": 1.5e-3},
               "half_batch": {"loss_gap": 1e-2, "grad_leaf_gap": 0.2,
                              "grad_norm_gap": 5e-3}}
    # The control reads under 3x the lower on the loss: the fault sets it.
    assert calibrate.upper_reading(summary, "loss_gap") == 1e-2
    assert calibrate.upper_reading(summary, "grad_leaf_gap") == 0.2
    assert calibrate.upper_reading(summary, "grad_norm_gap") is None
    limits = calibrate.limits_from(summary)
    assert limits == {"loss_gap": 1.6e-3, "grad_leaf_gap": 0.06}
