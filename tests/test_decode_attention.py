"""Pallas ragged decode-attention kernel vs dense reference (interpret
mode on CPU; the same code path compiles with Mosaic on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tfp_tpu.ops.decode_attention import (
    build_block_diag_q, extract_head_bands, fused_decode_attention,
)

S, SEQ, HKV, DH, H = 4, 512, 2, 32, 8  # group = 4
F = HKV * DH


def _rand(*shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _reference(q, ck, cv, lengths, scale, window=None):
    S_, H_, Dh = q.shape
    group = H_ // HKV
    out = np.zeros((S_, H_, Dh), np.float32)
    ckr = np.asarray(ck).reshape(S_, SEQ, HKV, DH)
    cvr = np.asarray(cv).reshape(S_, SEQ, HKV, DH)
    qn = np.asarray(q)
    for b in range(S_):
        n = int(lengths[b])
        for h in range(H_):
            kv = h // group
            k = ckr[b, :n, kv]  # [n, Dh]
            v = cvr[b, :n, kv]
            logit = k @ qn[b, h] * scale
            lo = 0
            if window is not None:
                lo = max(0, n - window)
            logit[:lo] = -np.inf
            w = np.exp(logit - logit.max())
            w[:lo] = 0.0
            w /= w.sum()
            out[b, h] = w @ v
    return out.reshape(S_, H_ * Dh)


def test_block_diag_roundtrip():
    q = _rand(S, H, DH, seed=1)
    wq = build_block_diag_q(q, HKV)
    assert wq.shape == (S, F, H)
    # column h must reproduce q[b, h] in its kv band and zeros elsewhere
    wqn = np.asarray(wq)
    qn = np.asarray(q)
    g = H // HKV
    for h in range(H):
        kv = h // g
        band = wqn[0, kv * DH : (kv + 1) * DH, h]
        np.testing.assert_allclose(band, qn[0, h])
        other = np.delete(wqn[0, :, h], np.s_[kv * DH : (kv + 1) * DH])
        assert np.all(other == 0)


@pytest.mark.parametrize("window", [None, 100])
def test_fused_decode_attention_matches_dense(window):
    """The per-slot manual-DMA kernel (read-only cache, VMEM-seeded
    current token) against the dense reference."""
    L = 3
    ck = _rand(L, S, SEQ, F, seed=8)
    cv = _rand(L, S, SEQ, F, seed=9)
    q = _rand(S, H, DH, seed=10) * 0.3
    new_k = _rand(S, F, seed=11)
    new_v = _rand(S, F, seed=12)
    lengths = jnp.asarray([1, 37, 256, 300], jnp.int32)  # incl current
    scale = 1.0 / np.sqrt(DH)
    rows = jnp.arange(S)
    ck2 = ck.at[1, rows, lengths - 1, :].set(new_k)
    cv2 = cv.at[1, rows, lengths - 1, :].set(new_v)
    out = fused_decode_attention(
        q, new_k, new_v, ck2, cv2, jnp.asarray(1, jnp.int32), lengths,
        HKV, scale=scale, window=window,
    )
    ref = _reference(q, ck2[1], cv2[1], lengths, scale, window)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


def test_fused_kernel_wrong_layer_untouched():
    """The layer scalar must select the right [L] slab."""
    L = 2
    ck = _rand(L, S, SEQ, F, seed=13)
    cv = _rand(L, S, SEQ, F, seed=14)
    q = _rand(S, H, DH, seed=15) * 0.3
    new_k = _rand(S, F, seed=16)
    new_v = _rand(S, F, seed=17)
    lengths = jnp.asarray([5, 9, 17, 33], jnp.int32)
    rows = jnp.arange(S)
    scale = 1.0 / np.sqrt(DH)
    outs = []
    for layer in range(L):
        ckw = ck.at[layer, rows, lengths - 1, :].set(new_k)
        cvw = cv.at[layer, rows, lengths - 1, :].set(new_v)
        out = fused_decode_attention(
            q, new_k, new_v, ckw, cvw, jnp.asarray(layer, jnp.int32),
            lengths, HKV, scale=scale,
        )
        ref = _reference(q, ckw[layer], cvw[layer], lengths, scale)
        np.testing.assert_allclose(np.asarray(out), ref,
                                   rtol=2e-4, atol=2e-4)
        outs.append(np.asarray(out))
    # different layers hold different K/V, so outputs must differ
    assert not np.allclose(outs[0], outs[1])


def test_fused_decode_attention_int8_cache():
    """int8 cache pages + per-row scales: the kernel dequantizes per page
    in VMEM (the quantized counterpart of the bf16 path; ref: llama.cpp
    cache_type_k/v q8_0)."""
    from localai_tfp_tpu.models.transformer import _quantize_rows

    L = 2
    ck = _rand(L, S, SEQ, F, seed=20)
    cv = _rand(L, S, SEQ, F, seed=21)
    q = _rand(S, H, DH, seed=22) * 0.3
    new_k = _rand(S, F, seed=23)
    new_v = _rand(S, F, seed=24)
    lengths = jnp.asarray([1, 37, 256, 300], jnp.int32)
    scale = 1.0 / np.sqrt(DH)
    rows = jnp.arange(S)
    ckq, ks = _quantize_rows(ck)  # int8 [L,S,SEQ,F], f32 [L,S,SEQ]
    cvq, vs = _quantize_rows(cv)
    # current rows: quantized into HBM (masked out by the kernel), exact
    # bf16 contribution seeded from VMEM
    nkq, nks = _quantize_rows(new_k)
    nvq, nvs = _quantize_rows(new_v)
    ckq = ckq.at[1, rows, lengths - 1, :].set(nkq)
    cvq = cvq.at[1, rows, lengths - 1, :].set(nvq)
    ks = ks.at[1, rows, lengths - 1].set(nks)
    vs = vs.at[1, rows, lengths - 1].set(nvs)
    out = fused_decode_attention(
        q, new_k, new_v, ckq, cvq, jnp.asarray(1, jnp.int32), lengths,
        HKV, scale=scale, cache_k_scale=ks, cache_v_scale=vs,
    )
    # reference: dequantized cache with the exact current row spliced in
    deq_k = np.asarray(ckq[1], np.float32) * np.asarray(ks[1])[..., None]
    deq_v = np.asarray(cvq[1], np.float32) * np.asarray(vs[1])[..., None]
    deq_k[rows, np.asarray(lengths) - 1] = np.asarray(new_k)
    deq_v[rows, np.asarray(lengths) - 1] = np.asarray(new_v)
    ref = _reference(q, jnp.asarray(deq_k), jnp.asarray(deq_v), lengths,
                     scale)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)


def test_engine_kernel_int8_cache_generates():
    """End-to-end: forced kernel engine + int8 cache generates
    deterministically, and its FIRST token matches the XLA int8 path
    (the first token comes from the shared XLA prefill, so it is
    computed identically; later tokens may legitimately diverge — the
    kernel seeds the current token's attention from exact rows in VMEM
    while the XLA path round-trips it through int8)."""
    import os

    from localai_tfp_tpu.engine.engine import GenRequest, LLMEngine
    from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
    from localai_tfp_tpu.models.llm_spec import tiny_spec
    from localai_tfp_tpu.models.transformer import init_params

    spec = tiny_spec(d_head=32, n_kv_heads=4, n_heads=4, max_position=512)
    assert spec.kv_dim % 128 == 0
    params = init_params(jax.random.PRNGKey(1), spec, dtype=jnp.float32)
    tok = ByteTokenizer()

    def gen(engine, n):
        q = engine.submit(GenRequest(
            prompt_ids=tok.encode("hello world", add_bos=True),
            max_tokens=n, temperature=0.0, ignore_eos=True))
        final = None
        while final is None:
            ev = q.get()
            if ev.done:
                final = ev
        # harvest-coalesced streaming: compare the generated TEXT (one
        # event may carry a multi-token span), not per-token events
        return final.full_text, final

    os.environ["LOCALAI_DECODE_KERNEL"] = "1"
    try:
        eng = LLMEngine(spec, params, tok, n_slots=2, max_seq=512,
                        cache_dtype="int8", autostart=False)
        assert eng._use_kernel and eng.cache.quantized
        eng.start()
        toks_a, ev = gen(eng, 12)
        toks_b, _ = gen(eng, 12)  # deterministic across runs
        eng.close()
    finally:
        os.environ.pop("LOCALAI_DECODE_KERNEL", None)
    assert ev.finish_reason == "length", ev.error
    assert toks_a == toks_b and ev.completion_tokens == 12
    eng2 = LLMEngine(spec, params, tok, n_slots=2, max_seq=512,
                     cache_dtype="int8", autostart=False)
    assert not eng2._use_kernel
    eng2.start()
    toks_x, ev2 = gen(eng2, 12)
    eng2.close()
    assert ev2.finish_reason == "length", ev2.error
    assert toks_x[0] == toks_a[0]  # first char: shared prefill path


def test_extract_head_bands_shape():
    out = _rand(S, H, F, seed=7)
    bands = extract_head_bands(out, HKV, DH)
    assert bands.shape == (S, H, DH)
    outr = np.asarray(out).reshape(S, HKV, H // HKV, HKV, DH)
    np.testing.assert_allclose(
        np.asarray(bands).reshape(S, HKV, H // HKV, DH),
        np.stack([outr[:, kv, :, kv] for kv in range(HKV)], 1),
    )
