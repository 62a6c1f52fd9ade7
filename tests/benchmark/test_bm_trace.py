"""The trace reduction on a synthetic event list laid out as a v5e
capture is (benchmark/lib/trace.py docstring): full HLO lines as op
names, nested whiles, one module event per executed program."""

import json
import os

import pytest

from benchmark.lib import layer_metrics
from benchmark.lib import trace as T

from . import helpers as H

MS = 1_000_000
L = 2  # layers of the toy configuration
KERNEL = ("%ragged_paged_attention.13 = f32[16,8,16,128]{3,2,1,0} "
          "custom-call(s32[16]{0} %broadcast.1)")
CONVERT = ("%convert.122 = bf16[16,8,16,128]{3,2,1,0} convert(f32[16,8,16,"
           "128]{3,2,1,0} %ragged_paged_attention.13)")
FUSION = "%fusion.431 = bf16[16,14336]{1,0} fusion(s8[4096,14336]{1,0} %p)"
MOE = ("%fusion.77 = bf16[16,1,4,128]{3,2,1,0} fusion(bf16[16,1,64]{2,1,0} "
       "%x, bf16[4,64,128]{2,1,0} %moe_gate)")


def decodek(t0, k):
    """One decodek program: an outer while over k steps, each an inner
    while over L layers of (kernel, convert, fusion)."""
    ops, t = [], t0 + 1 * MS
    outer_start = t
    for _ in range(k):
        inner_start = t
        for _ in range(L):
            ops.append([KERNEL, t, 1 * MS])
            ops.append([CONVERT, t + 1 * MS, 1000])
            ops.append([FUSION, t + 2 * MS, 2 * MS])
            t += 4 * MS
        ops.append(["%while.42 = (s32[]) while(%tuple.1)", inner_start,
                    t - inner_start])
    ops.append(["%while.41 = (s32[]) while(%tuple.0)", outer_start,
                t - outer_start])
    dur = t - t0 + 1 * MS
    return ["jit_dispatch_decodek(123)", t0, dur], ops


def mixed(t0, dur, moe=False):
    op = MOE if moe else \
        "%fusion.371 = bf16[16,512,14336]{2,1,0} fusion(bf16[16,512,4096] %h)"
    ops = [[op, t0, dur - 2 * MS],
           [KERNEL.replace(".13", ".9"), t0 + dur - 2 * MS, 2 * MS]]
    return ["jit_dispatch_mixed(77)", t0, dur], ops


@pytest.fixture(scope="module")
def capture():
    mods, ops = [], []
    for m, o in (decodek(0, 4), mixed(50 * MS, 20 * MS),
                 decodek(100 * MS, 2), mixed(150 * MS, 10 * MS, moe=True)):
        mods.append(m)
        ops += o
    return {"other_planes": ["/host:CPU"], "planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops},
            {"name": "Async XLA Ops", "events": [
                ["%copy-start.1 = ...", 0, 500 * MS]]}]},
        {"name": "/device:CUSTOM:Megascale Trace", "lines": []}]}


def test_only_chip_planes_with_xla_lines_count(capture):
    assert [p["name"] for p in T.chip_planes(capture)] == ["/device:TPU:0"]
    assert T.chip_planes({"planes": []}) == []


def test_union_merges_nested_and_touching_intervals():
    assert T.union([(0, 10), (2, 5), (10, 12), (20, 30)]) == [
        [0, 12], [20, 30]]


def test_busy_union_and_idle_share(capture):
    busy, window = T.busy_and_window(capture)
    # decodek(4): ops cover 1..33 ms; mixed 50..70; decodek(2) 101..117;
    # mixed 150..160 — async copies and module envelopes do not count
    assert busy == pytest.approx((32 + 20 + 16 + 10) / 1e3)
    assert window == pytest.approx(0.160)
    mdir = os.path.join(H.ROOT, "benchmark", "layer_metrics")
    idle = layer_metrics.evaluate(mdir, "device_idle_share", capture, {})
    assert idle == pytest.approx(100 * (1 - 78 / 160))
    assert layer_metrics.evaluate(mdir, "device_idle_share", None, {}) is None


def test_per_module_time_and_kind_prefixes(capture):
    assert T.module_seconds(capture, T.DECODE) == pytest.approx(
        (34 + 18) / 1e3)
    assert T.module_seconds(capture, T.PREFILL) == pytest.approx(0.030)
    assert len(T.module_events(capture, ("jit_dispatch_",))) == 4


def test_kernel_calls_are_matched_by_the_ops_own_name(capture):
    """``%convert.122`` mentions the kernel as an operand and is not a
    call of it (the first chip run counted it: twice the steps)."""
    assert T.own_name(CONVERT) == "convert.122"
    cfg = dict(H.TINY, num_hidden_layers=L)
    assert len(T.kernel_events(capture, cfg)) == 4 * L + 2 * L + 2
    dec = T.module_events(capture, T.DECODE)
    assert len(T.kernel_events(capture, cfg, dec)) == 6 * L
    steps, seconds = T.decode_steps(capture, cfg)
    assert steps == 6 and seconds == pytest.approx(0.052)


def test_attention_calls_are_those_the_model_file_names(capture):
    """A type whose attention runs under another op name: the steps of
    a decode program are counted by the prefixes its model file gives,
    and the roofline readers are fed by that file's counts — here the
    fixture type, its file brought into a ``models/`` of its own."""
    renamed = json.loads(json.dumps(capture).replace(
        "%ragged_paged_attention.", "%toy_latent_attention."))
    known = dict(H.TINY, num_hidden_layers=L)
    assert T.decode_steps(renamed, known)[0] == 0  # not that type's op
    cfg = dict(H.TINY_QWEN_MOE, num_hidden_layers=L)
    mdir = os.path.join(H.ROOT, "benchmark", "layer_metrics")
    run = {"config": cfg, "seconds": 1.0,
           "polls": [{"engine_kv_pages_in_use_count": [({}, 10.0)]}],
           "peaks": {"hbm_bytes_per_s": 1e9, "bf16_flops": 1e12}}
    with H.using_models(H.FIXTURE_MODELS) as models:
        assert T.decode_steps(renamed, cfg) == (6, pytest.approx(0.052))
        assert T.decode_steps(capture, cfg)[0] == 6  # either prefix
        ev = lambda n: layer_metrics.evaluate(mdir, n, renamed, run)  # noqa
        assert ev("attn_kernel_share") == pytest.approx(100 * 16 / 78)
        mod = models.of(cfg)
        # layer 0 a plain MLP, layer 1 four experts of which one row of
        # top-2 touches two, + the shared expert and its gate, in bf16
        d, f, fm, kv = 64, 128, 32, 32
        want = 2 * (L * (2 * d * d + 2 * d * kv) + 3 * d * f
                    + (3 * d * f + d) + 2 * 3 * d * fm + 512 * d) \
            + 4 * 4 * d
        assert mod.decode_weight_bytes(cfg, 1.0) == want
        page_bytes = 256 * 2 * kv * 2 * L
        assert ev("decode_hbm_roofline") == pytest.approx(
            100 * (want + 10 * page_bytes) / 1e9 / (0.052 / 6))
    with pytest.raises(FileNotFoundError, match="models/no_such_type.py"):
        T.decode_steps(renamed, dict(cfg, model_type="no_such_type"))


def test_self_time_subtracts_children():
    evs = [["outer", 0, 100], ["a", 10, 30],
           ["b", 50, 20], ["a2", 15, 5]]
    got = {e[0]: s for e, s in T.self_times(evs)}
    assert got == {"outer": 50, "a": 25, "a2": 5, "b": 20}


def test_top_ops_group_by_module_and_kind(capture):
    rows = dict(T.top_ops(capture, 10))
    assert rows["decodek/fusion"] == pytest.approx(6 * L * 2 / 1e3)
    assert rows["decodek/ragged_paged_attention"] == pytest.approx(
        6 * L * 1 / 1e3)
    assert rows["mixed/fusion"] == pytest.approx((18 + 8) / 1e3)
    # a loop's self time is what its body's ops leave uncovered
    assert rows["decodek/while"] == pytest.approx(6 * L * (MS - 1000) / 1e9)
    assert len(T.top_ops(capture, 3)) == 3


def test_idle_gaps_are_listed_longest_first_with_neighbours(capture):
    gaps = T.idle_gaps(capture, 5)
    assert gaps[0] == ["after_decodek_before_mixed", pytest.approx(0.033)]
    assert gaps[1] == ["after_mixed_before_decodek", pytest.approx(0.031)]
    assert gaps[2] == ["after_decodek_before_mixed", pytest.approx(0.017)]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)
    by = dict(T.idle_by_neighbours(capture, 5))
    assert by["after_decodek_before_mixed"] == pytest.approx(
        (17 + 33) / 1e3)


def test_trace_layer_metrics(capture):
    mdir = os.path.join(H.ROOT, "benchmark", "layer_metrics")
    cfg = dict(H.TINY_MOE, num_hidden_layers=L)
    page_bytes = 256 * 2 * 2 * 16 * 2 * L  # tokens x (K,V) x kv dim x bytes
    polls = [{"engine_kv_pages_in_use_count": [({}, 10.0)]}]
    log = [{"due": 0.0, "sent": 0.0, "prompt_tokens": 100,
            "prompt_tokens_served": 100, "completion_tokens": 50,
            "chunk_t": [0.1 + 0.01 * i for i in range(50)]}]
    log[0]["chunk_t"] = [0.25 + 0.01 * i for i in range(50)]
    prof = {"before": {}, "after": {}, "t_before": 0.2, "t_after": 0.9}
    run = {"config": cfg, "log": log, "polls": polls, "profile": prof,
           "seconds": 1.0,
           "peaks": {"hbm_bytes_per_s": 1e9, "bf16_flops": 1e12}}
    ev = lambda n: layer_metrics.evaluate(mdir, n, capture, run)  # noqa
    assert ev("decode_step_dev_ms") == pytest.approx(52 / 6)
    # the one request whose first chunk fell inside the traced span
    # (0.2 .. 0.36 s) brought 100 prompt tokens
    assert ev("prefill_dev_ms_ktok") == pytest.approx(30 / (100 / 1e3))
    assert ev("attn_kernel_share") == pytest.approx(100 * 16 / 78)
    from benchmark.lib import roofline
    # no row counters in this run: one row, which touches 2 of 4 experts
    floor = (roofline.decode_weight_bytes(cfg, 1.0) + 10 * page_bytes) / 1e9
    assert ev("decode_hbm_roofline") == pytest.approx(
        100 * floor / (0.052 / 6))
    # MoE ops are found by the expert stacks' shapes: [E=4, D=64, F=128]
    assert ev("moe_share") == pytest.approx(100 * 8 / 78)
    dense = dict(run, config=dict(H.TINY, num_hidden_layers=L))
    assert layer_metrics.evaluate(mdir, "moe_share", capture, dense) is None
    # 12 decode-step calls; one request with ~100 + tokens so far in cache
    got = ev("attn_kernel_roofline")
    assert 0 < got < 100


def test_roofline_bytes_from_shapes():
    from benchmark.lib import models, roofline

    with open(os.path.join(H.ROOT, "benchmark", "configs",
                           "mistral-7b-instruct-v0.3.json")) as f:
        mistral = json.load(f)
    # Mixtral-8x7B's published widths, 3 layers, experts served in bf16
    mixtral = dict(mistral, model_type="mixtral", vocab_size=32000,
                   num_hidden_layers=3, num_local_experts=8,
                   num_experts_per_tok=2)
    # int8: one byte a parameter; 7.11 G matrix parameters without the
    # embedding table
    assert roofline.decode_weight_bytes(mistral) == pytest.approx(
        7.11e9, rel=0.01)
    assert roofline.decode_weight_bytes(mistral, 16) == \
        roofline.decode_weight_bytes(mistral)
    # the floor bills the experts the routing HAS to touch: one row of
    # top-2 reads 2 of 8, sixteen rows 7.9 of 8 (uniform routing); per
    # layer 8 experts x 3 x 4096 x 14336 in bf16 = 2.82 GB, + int8
    # attention, + the int8 head
    touched = models.load("mixtral", H.MODELS).experts_touched
    assert touched(mixtral, 1) == pytest.approx(2.0)
    assert touched(mixtral, 16) == pytest.approx(8 * (1 - 0.75 ** 16))
    n = mixtral["num_hidden_layers"]
    assert roofline.decode_weight_bytes(mixtral, 1) == pytest.approx(
        n * (2.818e9 / 4 + 41.9e6) + 131e6, rel=0.01)
    assert roofline.decode_weight_bytes(mixtral, 1e9) == pytest.approx(
        n * (2.818e9 + 41.9e6) + 131e6, rel=0.01)
    assert roofline.kv_bytes_per_token(mistral) == 2 * 1024 * 32
    assert roofline.kv_bytes_per_token(mistral, layers=1) == 2048
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    assert roofline.min_seconds(8.19e9, 1e9, peak) == (
        pytest.approx(0.01), "memory")
    assert roofline.min_seconds(1e3, 197e12, peak)[1] == "compute"


def test_peaks_table_has_no_default():
    from benchmark.lib import peaks

    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")


# ---- a recorded capture (cut from the first chip run of PR 24) -----------


@pytest.fixture(scope="module")
def recorded():
    """One ``jit_dispatch_mixed`` and one ``jit_dispatch_decodek`` (k=8)
    program of Mistral-7B on the v5e, with every op event between them
    removed; op names cut to 160 characters."""
    import gzip

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "v5e_capture_sample.json.gz")
    with gzip.open(path) as f:
        return json.load(f)


def test_recorded_capture_reduces_as_the_chip_run_did(recorded):
    with open(os.path.join(H.ROOT, "benchmark", "configs",
                           "mistral-7b-instruct-v0.3.json")) as f:
        cfg = json.load(f)
    assert [p["name"] for p in T.chip_planes(recorded)] == ["/device:TPU:0"]
    dec = T.module_events(recorded, T.DECODE)
    assert len(dec) == 1 and len(T.module_events(recorded, T.PREFILL)) == 1
    # 8 steps x 32 layers: one kernel call per layer per step — and the
    # convert that names the kernel as its operand is not one
    assert len(T.kernel_events(recorded, cfg, dec)) == 256
    steps, seconds = T.decode_steps(recorded, cfg)
    assert steps == 8 and seconds * 1e3 / steps == pytest.approx(14.2, abs=0.1)
    busy, window = T.busy_and_window(recorded)
    assert busy == pytest.approx(0.2296, abs=1e-3) and busy < window
    ops = dict(T.top_ops(recorded, 10))
    assert ops["decodek/ragged_paged_attention"] == pytest.approx(
        0.0204, abs=1e-3)
    assert "mixed/fusion" in ops
    assert T.idle_gaps(recorded, 1)[0][0] == "after_mixed_before_decodek"
    mdir = os.path.join(H.ROOT, "benchmark", "layer_metrics")
    run = {"config": cfg, "polls": [
        {"engine_kv_pages_in_use_count": [({}, 40.0)]}],
        "peaks": {"hbm_bytes_per_s": 819e9}}
    share = layer_metrics.evaluate(mdir, "decode_hbm_roofline", recorded, run)
    # (7.11 GB of int8 weights + 0.67 GB of KV in 40 pages) / 819 GB/s = 9.5 ms
    # over the 14.2 ms a step took
    assert share == pytest.approx(66.8, abs=0.5) and share < 100
    assert layer_metrics.evaluate(mdir, "moe_share", recorded, run) is None
