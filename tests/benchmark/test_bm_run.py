"""run.py from outside: the device gate, the result line, and the whole
harness rehearsed on the CPU against a tiny configuration (server child,
load generator, /metrics deltas, result object) by calling past the
gate — run.py itself has no CPU mode."""

import json
import os
import subprocess
import sys
import time

import pytest

from . import helpers as H

RUN = [sys.executable, os.path.join(H.ROOT, "benchmark", "run.py"),
       "--workload", "mistral7b_batch_closed", "--seed", "2147483659",
       "--seconds", "2", "--trace", "0"]


def _env(**kw):
    env = dict(os.environ)
    env.update(kw)
    return env


def test_refuses_without_a_tpu_and_prints_no_result():
    p = subprocess.run(RUN, cwd=H.ROOT, env=_env(JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no CPU mode" in p.stderr


def test_refuses_in_a_directory_without_the_program(tmp_path):
    import shutil

    shutil.copy(os.path.join(H.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(H.ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    env = _env()
    env.pop("JAX_PLATFORMS", None)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py"] + RUN[2:], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_unknown_workload_is_an_error_not_a_result():
    from benchmark import run as B
    from benchmark.lib.children import HarnessFailure

    with pytest.raises((KeyError, HarnessFailure)):
        B.Cell(H.ROOT, "no_such_cell", 1, False, "t-unknown")


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = H.copy_benchmark(str(tmp_path_factory.mktemp("checkout")))
    H.add_cell(root, config_name="tiny", config=H.TINY,
               mix_name="tiny_open", mix=H.TINY_OPEN,
               cell_name="tiny_open",
               join=["tpot_p50_ms", "decode_rows_mean"])
    H.add_cell(root, config_name="tiny_moe", config=H.TINY_MOE,
               mix_name="tiny_closed", mix=H.TINY_CLOSED,
               cell_name="tiny_moe_closed",
               join=["tpot_p50_ms", "decode_rows_mean",
                     "kv_pages_peak_share", "first_use_loads"])
    # a model type the harness does not know, as a later PR brings it:
    # one new file under benchmark/models/, its configuration, its cell,
    # joined to EVERY listed metric (the roofline readers with them)
    H.add_model_file(root, "qwen2_moe")
    H.add_cell(root, config_name="tiny_qwen_moe", config=H.TINY_QWEN_MOE,
               mix_name="tiny_closed_b", mix=H.TINY_CLOSED,
               cell_name="tiny_new_type_closed", join=None)
    # a configuration that states its own engine path, and states it
    # wrongly for the CPU: only that clause of `correct` may fail
    H.add_cell(root, config_name="tiny_other_path",
               config=dict(H.TINY, expect={
                   "attention_path": "ragged_paged_kernel"}),
               mix_name="tiny_open_b", mix=H.TINY_OPEN,
               cell_name="tiny_other_path_open",
               join=["tpot_p50_ms", "decode_rows_mean"])
    return root


CPU = {"platform": "cpu", "attention_path": "paged_xla_gather",
       "kernel_ineligible": "platform cpu: Mosaic compiles on tpu only"}


@pytest.mark.parametrize("cell,trace,want,fails", [
    ("tiny_open", False, {"tpot_p50_ms", "setup_s"}, []),
    ("tiny_moe_closed", True, {"load_s", "warmup_s", "decode_rows_mean",
                               "kv_pages_peak_share", "first_use_loads"}, []),
    ("tiny_new_type_closed", True, {
        "load_s", "warmup_s", "decode_rows_mean", "kv_pages_peak_share",
        "first_use_loads", "mixed_fill_share", "program_load_stall_s"}, []),
    ("tiny_other_path_open", False, {"tpot_p50_ms", "setup_s"},
     ["attention_path"]),
], ids=["open_loop_end_to_end", "closed_loop_traced",
        "a_model_type_brought_as_a_file", "a_configurations_own_expect"])
def test_rehearsal_on_cpu(tiny_root, cell, trace, want, fails):
    from benchmark import run as B
    from benchmark.lib import models
    from benchmark.lib.children import CHILDREN

    t0 = time.perf_counter()
    try:
        res = B.run_cell(tiny_root, cell, 2**31 + 7, 4.0, trace, t0,
                         expect=CPU, probe=False)
    finally:
        CHILDREN.stop_all()
        models.use(H.MODELS)  # run_cell pointed it at the temp copy's
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert want <= set(res["metrics"]), res["metrics"]
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] is not None
    # the device is named as JAX reported it — and it is not a chip, so
    # nothing printed here is a device metric
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] >= 1
    if not trace:
        assert res["metrics"]["setup_s"]["value"] > 1.0
    # every clause of `correct` held (the route expected here is the
    # CPU's), but the one a configuration's own `expect` got wrong
    assert res["failed_clauses"] == fails
    assert res["correct"] is (not fails)
    assert set(res["compiled_in_window"]) == {"cache_entries",
                                              "first_use_loads"}
    # the closed mix asks for its traffic once through before the
    # measured episode; the open one does not
    if cell.endswith("_closed"):
        assert res["warm_episode"]["requests"] > 0
        assert res["warm_episode"]["malformed"] == 0
    else:
        assert res["warm_episode"] is None
    line = json.dumps(res)
    assert json.loads(line)["metrics"].keys() == res["metrics"].keys()
    run_dir = os.path.join(tiny_root, "benchmark", ".cache", "runs")
    logs = [d for d in os.listdir(run_dir) if d.startswith(cell)]
    assert logs and os.path.exists(
        os.path.join(run_dir, logs[0], "requests.jsonl"))


def test_result_line_shape_matches_the_contract():
    """The keys the driver reads, with --trace 0 and --trace 1."""
    dev0 = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
            "memory_peak_bytes": 13958643712}
    line = {"correct": True, "attempted": 160, "failed": 0,
            "metrics": {"ttft_p95_ms": {"value": 212.4, "unit": "ms"}},
            "device": dev0}
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(dev0) == {"platform", "kind", "count", "memory_peak_bytes"}

