"""BENCHMARK.json against the contract, and the claim that a cell, a
configuration, a mix and a metric are added by data alone."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark.lib import layer_metrics, models, traffic
from benchmark.lib import manifest as M

from . import helpers as H

MAN = M.load(H.ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
ALL_METRICS = MAN["end_to_end"] + MAN["per_layer"]


def test_manifest_is_sound():
    assert M.problems(MAN, H.ROOT) == []


def test_manifest_keys_and_size():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(H.ROOT, "BENCHMARK.json")) < 65536
    assert MAN["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= MAN["run_seconds"] <= 51
    # a full check of 24 cells fits the driver's allowance
    assert (2 + 14 * 24) * (MAN["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("m", ALL_METRICS, ids=lambda m: m["name"])
def test_metric_entry(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in M.SOURCES
    if m["name"].endswith("_roofline"):
        assert m["unit"] == "%"


@pytest.mark.parametrize("m", MAN["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_moves_a_metric_its_cells_report(m):
    e2e = {e["name"]: e for e in MAN["end_to_end"]}
    target = e2e[m["moves"]]
    cells = m.get("workloads", [w["name"] for w in MAN["workloads"]])
    for c in cells:
        assert "workloads" not in target or c in target["workloads"]
    assert layer_metrics.find(
        os.path.join(H.ROOT, "benchmark", "layer_metrics"), m["name"])


@pytest.mark.parametrize("w", MAN["workloads"], ids=lambda w: w["name"])
def test_cell_files_exist_and_load(w):
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    cfg = M.config_path(MAN, H.ROOT, w["config"])
    with open(cfg) as f:
        config = json.load(f)
    for key in ("source", "serving", "reduced", "assumed", "deployment",
                "weights_seed", "parity_prompts", "parity_tol",
                "parity_tol_reason"):
        assert key in config, key
    # its model type is a file that loads (never: one of a known list)
    mod = models.load(config["model_type"], H.MODELS)
    assert mod.tensors(config) and mod.kv_bytes_per_token(config) > 0
    assert set(config.get("expect", {})) <= {"attention_path",
                                             "kernel_ineligible"}
    mix = traffic.load_mix(M.traffic_path(H.ROOT, w["traffic"]),
                           M.cell_overrides(H.ROOT, w["name"]))
    sched = traffic.schedule(mix, 3, MAN["run_seconds"])
    assert sched["requests"]
    e2e = [m["name"] for m in M.metrics_of(MAN, "end_to_end", w["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert M.metrics_of(MAN, "per_layer", w["name"])


# a width as BENCHMARK.json's contract names one: never cut, never listed
WIDTH = re.compile(r"(hidden_size|intermediate_size|head_dim|_dim$|_rank$|"
                   r"num_attention_heads|num_key_value_heads|"
                   r"num_experts_per_tok|expansion)")


@pytest.mark.parametrize("c", MAN["configs"], ids=lambda c: c["name"])
def test_config_widths_are_the_published_ones(c):
    """Only depth may differ from the source, and only where listed.
    The source's own numbers are data beside the configuration
    (``<file>.published.json``, which a new configuration brings with
    it): every number there is the one run, unless the ``published``
    group owns up to the source's value, and no width is ever among
    those or in ``reduced``."""
    with open(os.path.join(H.ROOT, c["file"])) as f:
        config = json.load(f)
    with open(os.path.join(
            H.ROOT, c["file"][:-len(".json")] + ".published.json")) as f:
        src = json.load(f)
    assert src["source"] == config["source"] == c["source"]
    assert config["reduced"] == c["reduced"]
    differs = config.get("published", {})
    for key in list(c["reduced"]) + list(differs):
        assert not WIDTH.search(key), key
    numbers = {k: v for k, v in src["config"].items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)}
    assert {"hidden_size", "num_hidden_layers"} <= set(numbers)
    for key, want in numbers.items():
        if key not in config:
            continue  # a number the program does not read (dropout, init)
        if config[key] != want:
            assert differs.get(key) == want, key
    for key in config:  # every width run is one the source states
        if WIDTH.search(key) and key not in numbers:
            assert key == "head_dim" and config[key] == (
                numbers["hidden_size"] // numbers["num_attention_heads"])
    if "num_hidden_layers" in c["reduced"]:
        assert config["num_hidden_layers"] < numbers["num_hidden_layers"]
    else:
        assert config["num_hidden_layers"] == numbers["num_hidden_layers"]


def test_problems_are_reported():
    bad = json.loads(json.dumps(MAN))
    bad["per_layer"][0]["moves"] = "no_such_metric"
    bad["workloads"][0]["name"] = "has space"
    bad["end_to_end"][0]["unit"] = "tokens per second"
    out = "\n".join(M.problems(bad, H.ROOT))
    assert "no_such_metric" in out and "has space" in out
    assert "bad unit" in out


def test_add_cell_config_mix_and_metric_by_data_alone(tmp_path):
    """A later PR's move, in a temp copy: new files and appended
    entries, no edit to a file that is there."""
    root = H.copy_benchmark(str(tmp_path))
    before = H.snapshot(root)
    H.add_cell(root, config_name="tiny", config=H.TINY,
               mix_name="tiny_bursty",
               mix=dict(H.TINY_OPEN, arrivals={"dist": "gamma", "cv": 3},
                        burst={"every_s": 2, "count": 3,
                               "prompt_tokens": {"dist": "fixed",
                                                 "value": 90},
                               "output_tokens": {"dist": "fixed",
                                                 "value": 4}}),
               cell_name="tiny_bursty_cell",
               join=["tpot_p50_ms", "decode_rows_mean"])
    mdir = os.path.join(root, "benchmark", "layer_metrics")
    with open(os.path.join(mdir, "slots_busy_max.json"), "w") as f:
        json.dump({"source": "metrics", "family": "engine_slots_busy_count",
                   "reduce": "max_poll"}, f)
    with open(os.path.join(mdir, "requests_in_log.py"), "w") as f:
        f.write("def reduce(trace, run):\n    return len(run['log'])\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        man = json.load(f)
    for name, src in (("slots_busy_max", "program_counter"),
                      ("requests_in_log", "host_clock")):
        man["per_layer"].append({
            "name": name, "unit": "rows", "better": "higher",
            "source": src, "layer": "scheduler", "moves": "tpot_p50_ms",
            "workloads": ["tiny_bursty_cell"]})
    with open(path, "w") as f:
        json.dump(man, f)
    man = M.load(root)
    assert M.problems(man, root) == []
    # a configuration of a model type the harness has no file for: the
    # manifest check names the file to bring; brought, it is sound — and
    # still nothing that was there has changed
    new_type = dict(H.TINY_QWEN_MOE, model_type="toy_new_type")
    H.add_cell(root, config_name="tiny_new_type", config=new_type,
               mix_name="tiny_closed", mix=H.TINY_CLOSED,
               cell_name="tiny_new_type_cell", join=None)
    man = M.load(root)
    (lacks,) = M.problems(man, root)
    assert "benchmark/models/toy_new_type.py" in lacks and "mistral" in lacks
    H.add_model_file(root, "qwen2_moe", as_type="toy_new_type")
    assert M.problems(man, root) == []
    assert H.edited(before) == []  # nothing that was there changed
    mdl = models.load("toy_new_type",
                      os.path.join(root, "benchmark", "models"))
    assert mdl.decode_weight_bytes(new_type, 16) > \
        mdl.decode_weight_bytes(new_type, 1)
    cell = M.cell(man, "tiny_bursty_cell")
    mix = traffic.load_mix(M.traffic_path(root, cell["traffic"]))
    sched = traffic.schedule(mix, 5, 6.0)
    assert any(r.get("burst") for r in sched["requests"])
    names = [m["name"] for m in
             M.metrics_of(man, "per_layer", "tiny_bursty_cell")]
    assert {"slots_busy_max", "requests_in_log", "load_s"} <= set(names)
    assert "attn_kernel_roofline" not in names
    run = {"log": [1, 2, 3], "polls": [
        {"engine_slots_busy_count": [({"model": "tiny"}, 2.0)]},
        {"engine_slots_busy_count": [({"model": "tiny"}, 4.0)]}]}
    assert layer_metrics.evaluate(mdir, "slots_busy_max", None, run) == 4.0
    assert layer_metrics.evaluate(mdir, "requests_in_log", None, run) == 3.0


def test_a_cell_joined_to_every_listed_metric_passes_every_check(tmp_path):
    """The N-cell proof. A later PR's cell that reports ``tpot_p50_ms``
    appends its name to EVERY metric that carries a ``workloads`` list
    (a ``*roofline*`` metric that moves what the cell reports has to be
    reported there). Done here in a temp copy of the real manifest;
    then every test under tests/benchmark/ runs against that copy, so a
    test that pins a cell list, a count of cells or one configuration's
    sizes fails here, in the PR that writes it. The cell's model type
    is one more file too (a fixture the harness has no file for), so a
    test that pins the set of model types fails here as well."""
    if os.environ.get("BM_TESTS_ROOT"):
        pytest.skip("the inner run of this very proof")
    root = H.copy_benchmark(str(tmp_path))
    before = H.snapshot(root)
    H.add_model_file(root, "qwen2_moe")
    H.add_cell(root, config_name="ncell_tiny", config=H.TINY_QWEN_MOE,
               mix_name="ncell_closed", mix=H.TINY_CLOSED,
               cell_name="tiny_everywhere", join=None)
    man = M.load(root)
    listed = [m for m in man["end_to_end"] + man["per_layer"]
              if "workloads" in m]
    assert listed and all(m["workloads"][-1] == "tiny_everywhere"
                          and len(m["workloads"]) >= 2 for m in listed)
    assert M.problems(man, root) == []
    assert H.edited(before) == []
    # the new cell reports every per-layer metric and what each moves
    assert [m["name"] for m in M.metrics_of(man, "per_layer",
                                            "tiny_everywhere")] == \
        [m["name"] for m in man["per_layer"]]
    assert {"tpot_p50_ms", "setup_s"} <= {
        m["name"] for m in M.metrics_of(man, "end_to_end",
                                        "tiny_everywhere")}
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    env["BM_TESTS_ROOT"] = root
    # the rehearsal builds temp cells of its own from the same copy and
    # takes a minute: it is N cells by construction, and left out
    p = subprocess.run(
        [sys.executable, "-m", "pytest",
         os.path.join(H.REPO, "tests", "benchmark"), "-m", "not slow",
         "-k", "not rehearsal", "-p", "no:cacheprovider", "-p",
         "no:randomly"],
        cwd=H.REPO, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-1000:]
    assert " passed" in p.stdout and " failed" not in p.stdout
