"""JAX LLM worker: the TPU-native counterpart of the reference's llama.cpp
gRPC backend (ref: backend/cpp/llama/grpc-server.cpp — LoadModel :2467,
Predict :2542, PredictStream :2488, Embedding :2579, TokenizeString :2603,
GetMetrics, Health :2461). One worker owns one LLMEngine over one loaded
checkpoint.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Iterator, Optional

import jax
import jax.numpy as jnp

from ..config import knobs
from ..engine.engine import GenRequest, LLMEngine, StreamEvent
from ..engine.tokenizer import Tokenizer, load_tokenizer
from ..grammars.native import make_constraint
from ..models.hf_loader import load_params
from ..models.lora import merge_lora
from ..models.llm_spec import LLMSpec
from .base import (
    Backend,
    EmbeddingResult,
    MetricsResponse,
    ModelLoadOptions,
    PredictOptions,
    Reply,
    Result,
    StatusResponse,
    TokenizationResponse,
)

_DTYPES = {
    "bfloat16": jnp.bfloat16,
    "bf16": jnp.bfloat16,
    "float32": jnp.float32,
    "f32": jnp.float32,
    "float16": jnp.bfloat16,  # fp16 is not a TPU-native dtype; use bf16
    "f16": jnp.bfloat16,
}

# KV-cache-only dtypes (ref: cache_type_k/v q8/f16 — grpc-server.cpp
# :2337-2342): int8 rows with per-row scales
_KV_DTYPES = {**_DTYPES, "int8": jnp.int8, "i8": jnp.int8,
              "q8": jnp.int8, "q8_0": jnp.int8}


class JaxLLMBackend(Backend):
    """Serves chat/completion/embeddings/tokenize for HF checkpoints."""

    def __init__(self, role: Optional[str] = None) -> None:
        self.engine: Optional[LLMEngine] = None
        self.tokenizer: Optional[Tokenizer] = None
        self.spec: Optional[LLMSpec] = None
        self._state = "UNINITIALIZED"
        self._grammar_cache: dict[str, object] = {}
        self._lock = threading.Lock()
        # multihost role override ("leader"/"follower"/"solo"); None reads
        # the process-wide multihost.role()
        self._role = role
        # multimodal: (VisionSpec, VisionParams, mm_info) for checkpoints
        # with a vision tower (gemma3), else None
        self.vision: Any = None
        self._quantized = False  # int8 weight-only serving mode
        self.mamba: Any = None  # (MambaSpec, params) — SSM family
        self.rwkv: Any = None  # (RwkvSpec, params) — RWKV recurrent
        # family (ref fixture tests/models_fixtures/rwkv.yaml)
        self._artifact_thread: Any = None  # deferred quant-cache write
        self._artifact_abort = threading.Event()
        self.load_mode = "unknown"  # "artifact" | "full" after a load
        self.load_breakdown: dict = {}  # phase-timing breakdown of the
        # last load (models/load_timing.py): read/dequant/transfer/
        # compile/warmup seconds + total. Surfaced by /backend/monitor
        # and bench.py extra.checkpoint_load_breakdown.

    # ------------------------------------------------------------- lifecycle

    def _abort_pending_artifact(self) -> None:
        """A quant-cache drain still in flight pins the OLD device tree
        (7.5 GB at 8B) and contends on the transfer link — both fatal
        to a reload on a 16 GB chip. Abandon it before proceeding."""
        t = self._artifact_thread
        if t is not None and t.is_alive():
            self._artifact_abort.set()
            t.join(timeout=30)
            if t.is_alive():  # stuck in one huge pull or save_file
                import logging

                logging.getLogger(__name__).warning(
                    "quant artifact writer did not stop within 30s; "
                    "proceeding — expect transfer-link/host-RAM "
                    "contention until it exits")
        self._artifact_thread = None

    def load_model(self, opts: ModelLoadOptions) -> Result:
        from ..parallel import multihost

        channel = multihost.active_channel()
        role = self._role or multihost.role()
        with self._lock:
            # cheap validations FIRST: a typo'd knob must fail in
            # milliseconds, before checkpoint IO, before the multihost
            # load broadcast fans the doomed load out to followers, and
            # before a doomed load abandons the PREVIOUS model's pending
            # artifact write
            quant = (opts.quantization or "").lower()
            if quant and quant not in ("int8", "q8", "q8_0", "w8",
                                       "int8_full", "none", "f16", "fp16",
                                       "bf16", "bfloat16"):
                self._state = "ERROR"
                return Result(
                    False,
                    f"load failed: unsupported quantization "
                    f"'{opts.quantization}' (supported: int8, int8_full)")
            model_dir = opts.model
            if not os.path.isabs(model_dir):
                model_dir = os.path.join(opts.model_path or "", model_dir)
            if model_dir.rstrip("/").endswith(".exl2") or os.path.isfile(
                    os.path.join(model_dir, "job_new.json")):  # exl2 dir
                self._state = "ERROR"
                return Result(
                    False,
                    "load failed: EXL2 is exllamav2's CUDA-kernel-"
                    "specific storage and is not served on TPU (see "
                    "the EXL2 won't-fix entry in PARITY.md); point "
                    "parameters.model at the model's GGUF or "
                    "safetensors release and set quantization: int8 "
                    "for the equivalent quantized serving mode")
            is_gguf = model_dir.endswith(".gguf")
            if (not os.path.isdir(model_dir) if not is_gguf
                    else not os.path.isfile(model_dir)):
                # validate BEFORE broadcasting: a typo'd model name must
                # stay leader-local, not fan a doomed load out to the slice
                self._state = "ERROR"
                return Result(
                    False,
                    f"load failed: model not found: {model_dir}",
                )
            self._abort_pending_artifact()  # the real load begins here
            from ..models.load_timing import LoadPhases

            phases = LoadPhases()
            self.load_breakdown = {}
            if channel is not None and role == "leader":
                # followers load the identical checkpoint from their own
                # disk (in parallel with ours) and then replay this
                # engine's dispatch records. Published under _lock so
                # concurrent reloads keep one total load order; a failure
                # below publishes a compensating unload.
                channel.publish("load", opts)
            try:
                self._state = "BUSY"
                # a reload over a previous family must not leave the old
                # route reachable (predict() dispatches on self.mamba
                # first — same invariant tts.py keeps for its slots)
                self.mamba = None
                self.rwkv = None
                dtype = _DTYPES.get((opts.dtype or "bfloat16").lower(),
                                    jnp.bfloat16)
                # quantized loads STAGE ON HOST CPU: the full-precision
                # tree of an 8B model (~16 GB bf16) ResourceExhausts a
                # 16 GB chip before quantization could halve it, so the
                # checkpoint loads + LoRA-merges + quantizes on host and
                # only the int8 tree ships to the accelerator (caught by
                # the bench's disk-loaded 8B leg, r5)
                import contextlib

                will_quant = quant in ("int8", "q8", "q8_0", "w8",
                                       "int8_full")

                def staged():
                    return (jax.default_device(jax.devices("cpu")[0])
                            if will_quant else contextlib.nullcontext())

                defer_commit = False  # streaming device commit
                artifact_hit = False  # pre-quantized tree from cache
                artifact_file = None
                artifact_host = {}  # host mirror kept from the artifact
                # read — seeds the weight pager's warm tier for free
                pending_artifact = None  # written after warmup
                params = None
                load_ledger = None  # load-time HBM attribution (the
                # engine builds its own serving ledger at construction)
                if is_gguf:
                    # GGUF: dequantize-on-load (ref: the reference's
                    # primary format — initializers.go:498-559); the
                    # tokenizer rides inside the file. Header parsed
                    # ONCE (the 100k+-token vocab dominates parse time).
                    from ..models.gguf import (
                        GGUFFile, load_gguf_params, tokenizer_from_gguf,
                    )

                    hf_state = None
                    with phases.timed("read_s"):  # vocab-heavy header
                        gf = GGUFFile(model_dir)
                    gf.phases = phases  # per-tensor read/dequant split
                    with staged():
                        self.spec, params = load_gguf_params(
                            model_dir, dtype=dtype, gf=gf)
                else:
                    from ..models.hf_loader import load_hf_state

                    with phases.timed("read_s"):
                        hf_state = load_hf_state(model_dir)
                    from ..models.mamba import is_mamba_config
                    from ..models.rwkv import is_rwkv_config

                    if is_rwkv_config(hf_state[0]):
                        # RWKV: recurrent generate path like mamba (no
                        # KV cache; ref serves RWKV via llama.cpp —
                        # tests/models_fixtures/rwkv.yaml)
                        from ..models.rwkv import load_rwkv

                        if self.engine is not None:
                            self.engine.close()
                            self.engine = None
                        self.rwkv = load_rwkv(model_dir, dtype=dtype)
                        self.tokenizer = load_tokenizer(model_dir)
                        self._state = "READY"
                        self.load_mode = "full"
                        self.load_breakdown = phases.as_dict()
                        return Result(True, "rwkv model loaded")
                    if is_mamba_config(hf_state[0]):
                        # SSM family (ref: transformers backend
                        # MambaForCausalLM, backend.py:24,248): no KV
                        # cache — recurrent generate path, not the
                        # slot engine
                        from ..models.mamba import load_mamba

                        if self.engine is not None:  # reload over an
                            self.engine.close()  # attention model
                            self.engine = None
                        self.mamba = load_mamba(model_dir, dtype=dtype)
                        self.tokenizer = load_tokenizer(model_dir)
                        self._state = "READY"
                        self.load_mode = "full"
                        self.load_breakdown = phases.as_dict()
                        return Result(True, "mamba model loaded")
                    # single-chip quantized loads stream raw leaves to
                    # the chip and fuse cast+transpose+quantize there
                    # (models/staging.py) — the host-staged eager
                    # pipeline measured ~10 min on an 8B where this is
                    # tens of seconds; an on-disk int8 artifact
                    # (models/artifact_cache.py) makes repeat loads skip
                    # the bf16 tree entirely, like the reference's
                    # pre-quantized GGUF flow
                    defer_commit = (
                        will_quant and not opts.mesh
                        and not opts.lora_adapters)
                    if defer_commit:
                        from ..models.artifact_cache import (
                            artifact_path, try_load)
                        from ..models.llm_spec import spec_from_hf_config

                        artifact_file = artifact_path(
                            model_dir, quant, str(dtype.__name__))
                        # the artifact read streams every leaf through
                        # host RAM anyway; keep that copy as the weight
                        # pager's warm mirror so the model's first
                        # demotion is a zero-DMA drop
                        params = try_load(artifact_file,
                                          jax.devices()[0],
                                          phases=phases,
                                          keep_host=artifact_host)
                        if params is not None:
                            self.spec = spec_from_hf_config(hf_state[0])
                            if "lm_head" not in params:
                                # mirror load_params' correction for
                                # checkpoints that tie despite config
                                # (hf_loader tie fallback) — the
                                # artifact has no lm_head leaf then
                                object.__setattr__(
                                    self.spec, "tie_word_embeddings",
                                    True)
                            artifact_hit = True
                            defer_commit = False
                    if params is None:
                        with staged():
                            self.spec, params = load_params(
                                model_dir, dtype=dtype, state=hf_state,
                                defer_transpose=defer_commit,
                                phases=phases)
                # merge LoRA adapters at load (ref: llama.cpp LoRA apply
                # via LoadModel — proto LoraAdapter/LoraScale)
                with staged():
                    for i, adir in enumerate(opts.lora_adapters):
                        if not os.path.isabs(adir):
                            adir = os.path.join(opts.model_path or "",
                                                adir)
                        # an explicit 0.0 scale disables the adapter;
                        # only a MISSING entry defaults to 1.0
                        scale = (float(opts.lora_scales[i])
                                 if i < len(opts.lora_scales) else 1.0)
                        if scale == 0.0:
                            continue
                        params, n = merge_lora(self.spec, params, adir,
                                               scale=scale)
                if is_gguf:
                    # no silent raw-byte fallback: a 128k-vocab model
                    # with a broken embedded vocab must fail the load
                    self.tokenizer = tokenizer_from_gguf(gf)
                else:
                    self.tokenizer = load_tokenizer(model_dir)
                if is_gguf:
                    self.vision = None  # gguf carries no mmproj tower
                else:
                    try:
                        from ..models.hf_loader import load_multimodal

                        self.vision = load_multimodal(
                            model_dir, dtype=dtype, state=hf_state)
                    except Exception as ve:
                        # text-only serving still works, but a genuinely
                        # multimodal checkpoint losing its tower must be
                        # operator-visible, not silent
                        import logging

                        logging.getLogger(__name__).warning(
                            "vision tower load failed for %s: %r — "
                            "serving text-only, image parts will be "
                            "ignored", model_dir, ve)
                        self.vision = None
                kv_dtype = _KV_DTYPES.get(
                    (opts.kv_cache_dtype or opts.dtype or "bfloat16").lower(),
                    dtype,
                )
                self._quantized = will_quant  # ONE predicate: staging
                # and quantization must agree (host-committed params
                # with no quantize, or device-committed full-precision
                # 8B, are both failure modes)
                if defer_commit:  # implies self._quantized
                    # streaming commit: raw leaves -> device, fused
                    # cast+transpose+quantize there; the int8 tree
                    # persists for the next load AFTER warmup (below) —
                    # the 7.5 GB device->host drain must not contend
                    # with warmup or first requests
                    from ..models.staging import commit_deferred
                    from ..telemetry import hbm_ledger

                    if knobs.flag("LOCALAI_HBM_LEDGER"):
                        load_ledger = hbm_ledger.HBMLedger(opts.model)
                    params = commit_deferred(
                        params, dtype, jax.devices()[0],
                        quantize=True,
                        quantize_embeddings=quant == "int8_full",
                        phases=phases, ledger=load_ledger)
                    pending_artifact = artifact_file
                elif self._quantized and not artifact_hit:
                    # AFTER LoRA merge: adapters fold into full-precision
                    # weights first, then the projections quantize.
                    # int8_full also quantizes embed/lm_head (~2 GB on an
                    # 8B — the batch-64-on-one-chip mode). Runs inside
                    # the host staging (see staged()); only the int8
                    # tree then ships to the accelerator.
                    from ..models.quant import quantize_params

                    with staged(), phases.timed("dequant_s"):
                        params = quantize_params(
                            params, embeddings=quant == "int8_full")
                        params = jax.block_until_ready(params)
                    if opts.mesh:
                        pass  # shard_params places shards itself
                    else:
                        with phases.timed("transfer_s"):
                            params = jax.device_put(
                                params, jax.devices()[0])
                            params = jax.block_until_ready(params)
                mesh = None
                if opts.mesh:
                    from ..parallel.mesh import make_mesh

                    mesh = make_mesh(opts.mesh)
                draft = None
                if opts.draft_model:
                    ddir = opts.draft_model
                    if not os.path.isabs(ddir):
                        ddir = os.path.join(opts.model_path or "", ddir)
                    if ddir.endswith(".gguf"):
                        from ..models.gguf import load_gguf_params

                        draft = load_gguf_params(ddir, dtype=dtype)
                    else:
                        draft = load_params(ddir, dtype=dtype)
                with phases.timed("compile_s"):
                    self.engine = LLMEngine(
                        self.spec,
                        params,
                        self.tokenizer,
                        n_slots=max(1, opts.batch_slots),
                        max_seq=opts.context_size,
                        cache_dtype=kv_dtype,
                        decode_steps=int(opts.extra.get("decode_steps",
                                                        8)),
                        latency_target_ms=(
                            float(opts.extra["latency_target_ms"])
                            if opts.extra.get("latency_target_ms")
                            is not None
                            else None),
                        mesh=mesh,
                        draft=draft,
                        n_draft=opts.n_draft or 4,
                        channel=channel if role == "leader" else None,
                        follower=role == "follower",
                        tag=opts.model,
                        # disagg shares one tree between the prefill
                        # and decode engines by reference — weight
                        # paging would strand one side's dispatches
                        weight_paging=(
                            False if knobs.flag("LOCALAI_DISAGG")
                            else None),
                    )
                    pager = getattr(self.engine, "_pager", None)
                    if pager is not None and artifact_hit \
                            and artifact_host:
                        # artifact loads never merge LoRA (defer_commit
                        # excludes adapters), so the captured host tree
                        # mirrors engine.params exactly
                        pager.seed_host(artifact_host,
                                        self.engine.params)
                    artifact_host = {}
                    self.engine.start()
                if (knobs.flag("LOCALAI_DISAGG")
                        and mesh is None and draft is None
                        and channel is None and role != "follower"
                        and getattr(self.engine, "_paged", False)):
                    # disaggregated serving: a prefill-tuned sibling
                    # engine shares the weights, and the router front
                    # door relays long prompts through the KV page
                    # migration protocol (engine/kv_migrate.py). Off
                    # by default — the plain engine path is untouched.
                    from ..engine.kv_migrate import (DisaggRouter,
                                                     build_prefill_engine)

                    with phases.timed("disagg_s"):
                        prefill = build_prefill_engine(
                            self.spec, params, self.tokenizer,
                            decode=self.engine, cache_dtype=kv_dtype,
                            tag=opts.model)
                        prefill.start()
                        self.engine = DisaggRouter(prefill, self.engine)
                if (role != "follower"
                        and knobs.flag("LOCALAI_WARMUP")):
                    # precompile the dispatch-variant set: a cold jit
                    # landing mid-request is a ~13s TTFT outlier at 8B
                    # scale (engine.warmup docstring); an identical
                    # variant set already in the persistent compile
                    # cache skips the pass (warmup_reused)
                    with phases.timed("warmup_s"):
                        self.engine.warmup()
                # which load path this load ACTUALLY took (bench and
                # operators read it; inferring it from artifact-file
                # existence mislabels version-mismatch/corrupt misses)
                self.load_mode = "artifact" if artifact_hit else "full"
                self.load_breakdown = {
                    **phases.as_dict(),
                    "load_mode": self.load_mode,
                    "warmup_reused": bool(
                        getattr(self.engine, "warmup_reused", False)),
                }
                if pending_artifact:
                    from ..models.artifact_cache import save_async

                    eng = self.engine

                    def _engine_idle() -> bool:
                        # _has_work covers queued requests and in-flight
                        # dispatches, not just occupied slots
                        return eng is None or not eng._has_work()

                    self._artifact_abort = threading.Event()
                    self._artifact_thread = save_async(
                        pending_artifact, params, idle=_engine_idle,
                        abort=self._artifact_abort)
                self._state = "READY"
                return Result(True, "model loaded")
            except Exception as e:
                self._state = "ERROR"
                from ..telemetry import hbm_ledger

                if hbm_ledger.looks_like_oom(e):
                    # loader-path OOM forensics: ledger attribution of
                    # whatever was committed before the allocation
                    # failed, plus device stats (best-effort dump)
                    eng = self.engine
                    hbm_ledger.dump_post_mortem(
                        getattr(eng, "state_dir", None)
                        or hbm_ledger.default_state_dir(),
                        opts.model, e,
                        ledger=(getattr(eng, "_ledger", None)
                                or load_ledger))
                if channel is not None and role == "leader":
                    # release the followers' (possibly successful) copy;
                    # leader and followers must agree the model is absent
                    channel.publish("unload", {"model": opts.model})
                return Result(False, f"load failed: {e}")

    def shutdown(self) -> None:
        from ..parallel import multihost

        self._abort_pending_artifact()
        tag = self.engine.tag if self.engine is not None else ""
        if self.engine is not None:
            # close BEFORE broadcasting unload: the scheduler thread must
            # drain so no dispatch record trails the followers' teardown
            self.engine.close()
            self.engine = None
        channel = multihost.active_channel()
        if channel is not None and tag and \
                (self._role or multihost.role()) == "leader":
            channel.publish("unload", {"model": tag})
        self._state = "UNINITIALIZED"

    def health(self) -> bool:
        return self._state in ("READY", "BUSY")

    def status(self) -> StatusResponse:
        """State + memory breakdown (ref: backend.proto StatusResponse
        memory fields served by /backend/monitor)."""
        mem: dict[str, int] = {}
        if self.engine is not None:
            try:
                mem["kv_cache_bytes"] = int(
                    self.engine.cache.k.size * self.engine.cache.k.dtype.itemsize
                ) * 2
                mem["params_bytes"] = int(sum(
                    int(p.size) * p.dtype.itemsize
                    for p in jax.tree_util.tree_leaves(self.engine.params)
                ))
                pager = getattr(self.engine, "_pager", None)
                if pager is not None:
                    # weight residency split: a warm model reports
                    # params_bytes 0 (nothing on device) and its tree
                    # under weights_warm_bytes
                    mem["weights_hot_bytes"] = int(pager.device_bytes())
                    mem["weights_warm_bytes"] = int(pager.host_bytes())
            except Exception as e:
                # status must never fail, but a half-built engine
                # should say so rather than report empty memory
                mem["error"] = repr(e)
        return StatusResponse(state=self._state, memory=mem)

    def busy(self) -> bool:
        return self.engine is not None and any(
            s.active for s in self.engine.slots
        )

    def demote_weights(self) -> Optional[str]:
        """Page this model's weights out to host RAM (watchdog demote
        mode and the admin API). Returns "demoted" (async demotion
        started), "busy" (a transition is in flight or the engine has
        work), "warm" (already paged out), or None (no pager: meshed /
        disagg / paging off)."""
        pager = getattr(self.engine, "_pager", None)
        if pager is None:
            return None
        st = pager.state
        if st == "hot":
            return ("demoted"
                    if pager.request_demote(reason="watchdog")
                    else "busy")
        if st in ("demoting", "promoting"):
            return "busy"
        return "warm"

    def weight_residency(self) -> Optional[dict]:
        """Pager snapshot for /backend/monitor (None when paging is
        off for this engine)."""
        pager = getattr(self.engine, "_pager", None)
        return None if pager is None else pager.stats()

    # ------------------------------------------------------------- inference

    def _splice_images(self, prompt: str, images: list[bytes]):
        """Expand [img-N] markers into <boi> + mm_tokens soft tokens +
        <eoi> id runs and encode the images through the vision tower
        (ref: the llava mmproj embedding path, grpc-server.cpp:1476-1502;
        marker convention: pkg/templates/multimodal.go). Returns
        (prompt_ids, soft_embeds [n_soft, D] f32, soft_positions [n_soft])."""
        import re as _re

        import numpy as np

        from ..models.vision import encode_images_jit, preprocess_image

        vspec, vparams, mm = self.vision
        pix = np.stack([
            preprocess_image(b, mm["image_size"],
                             mm.get("family", "siglip")) for b in images
        ])
        emb = self.engine.params["embed"]
        dtype = emb.q.dtype if hasattr(emb, "q") else emb.dtype
        if dtype == jnp.int8:  # quantized table: compute stays bf16
            dtype = jnp.bfloat16
        soft_all = np.asarray(
            encode_images_jit(vspec, vparams,
                              jnp.asarray(pix).astype(dtype))
            .astype(jnp.float32)
        )  # [n_images, mm_tokens, D]
        parts = _re.split(r"\[img-(\d+)\]", prompt)
        if len(parts) == 1:
            # no markers (template didn't place them): prepend the images
            parts = [""]
            for i in range(len(images)):
                parts += [str(i), prompt if i == len(images) - 1 else ""]
        ids = self.tokenizer.encode(parts[0], add_bos=True)
        positions: list[int] = []
        rows: list[np.ndarray] = []
        for j in range(1, len(parts), 2):
            img_i = int(parts[j])
            text = parts[j + 1]
            if img_i >= len(images):
                # user-typed [img-N] with no such image: keep it (and the
                # text after it) as literal prompt text, never drop input
                ids.extend(self.tokenizer.encode(
                    f"[img-{parts[j]}]" + text, add_bos=False))
                continue
            if mm.get("boi_token") is not None:
                ids.append(mm["boi_token"])
            start = len(ids)
            ids.extend([mm["image_token"]] * mm["mm_tokens"])
            positions.extend(range(start, start + mm["mm_tokens"]))
            rows.append(soft_all[img_i])
            if mm.get("eoi_token") is not None:
                ids.append(mm["eoi_token"])
            if text:
                ids.extend(self.tokenizer.encode(text, add_bos=False))
        if not rows:  # only bogus markers: plain text request
            return ids, None, None
        return (ids, np.concatenate(rows).astype(np.float32),
                np.asarray(positions, np.int32))

    def _to_request(self, opts: PredictOptions) -> GenRequest:
        assert self.engine is not None and self.tokenizer is not None
        soft_embeds = soft_positions = None
        if opts.images and self.vision is not None:
            prompt_ids, soft_embeds, soft_positions = self._splice_images(
                opts.prompt, opts.images)
        else:
            prompt_ids = self.tokenizer.encode(opts.prompt, add_bos=True)
        constraint = None
        if opts.grammar:
            key = (opts.grammar, tuple(opts.grammar_triggers or ()))
            constraint = self._grammar_cache.get(key)
            if constraint is None:
                # native C++ engine when built; Python fallback otherwise
                constraint = make_constraint(opts.grammar, self.tokenizer,
                                             triggers=opts.grammar_triggers)
                if len(self._grammar_cache) < 32:
                    self._grammar_cache[key] = constraint
        return GenRequest(
            prompt_ids=prompt_ids,
            max_tokens=opts.tokens or 2048,
            temperature=opts.temperature,
            top_k=opts.top_k,
            top_p=opts.top_p,
            min_p=opts.min_p,
            repeat_penalty=opts.repeat_penalty,
            repeat_last_n=opts.repeat_last_n,
            frequency_penalty=opts.frequency_penalty,
            presence_penalty=opts.presence_penalty,
            typical_p=opts.typical_p if opts.typical_p > 0 else 1.0,
            mirostat=opts.mirostat,
            mirostat_tau=opts.mirostat_tau if opts.mirostat_tau > 0 else 5.0,
            mirostat_eta=opts.mirostat_eta if opts.mirostat_eta > 0 else 0.1,
            seed=opts.seed,
            stop=list(opts.stop_prompts),
            ignore_eos=opts.ignore_eos,
            logit_bias=opts.logit_bias or None,
            constraint=constraint,
            prompt_cache_path=opts.prompt_cache_path,
            prompt_cache_all=opts.prompt_cache_all,
            prompt_cache_ro=opts.prompt_cache_ro,
            correlation_id=opts.correlation_id,
            timeout_s=max(0.0, opts.timeout_s),
            prefix_chain=tuple(opts.prefix_chain or ()),
            soft_embeds=soft_embeds,
            soft_positions=soft_positions,
            **({"id": opts.request_id} if opts.request_id else {}),
        )

    def cancel(self, request_id: str) -> None:
        if self.engine is not None:
            self.engine.cancel(request_id)

    def _recurrent_reply(self, opts: PredictOptions) -> Reply:
        import time as _time

        if self.rwkv is not None:
            from ..models.rwkv import generate

            spec, params = self.rwkv
        else:
            from ..models.mamba import generate

            spec, params = self.mamba
        ids = self.tokenizer.encode(opts.prompt, add_bos=True)
        t0 = _time.perf_counter()
        eos = next(iter(getattr(self.tokenizer, "eos_ids", []) or []),
                   None)
        toks = generate(
            spec, params, ids, opts.tokens or 256,
            temperature=opts.temperature, seed=opts.seed or 0,
            eos_id=None if opts.ignore_eos else eos,
        )
        out = [int(t) for t in toks]
        finish = "stop"
        if eos is not None and out and out[-1] == eos:
            out = out[:-1]
        elif len(out) >= (opts.tokens or 256):
            finish = "length"
        text = self.tokenizer.decode(out)
        for stop in opts.stop_prompts or []:
            i = text.find(stop)
            if i >= 0:
                text = text[:i]
                finish = "stop"
        return Reply(
            message=text, tokens=len(out), prompt_tokens=len(ids),
            finish_reason=finish,
            timing_token_generation=(_time.perf_counter() - t0) * 1e3,
        )

    def predict(self, opts: PredictOptions) -> Reply:
        if self.mamba is not None or self.rwkv is not None:
            return self._recurrent_reply(opts)
        if self.engine is None:
            return Reply(error="model not loaded")
        ev = self.engine.generate(self._to_request(opts))
        return _final_reply(ev)

    def stream_queue(self, opts: PredictOptions):
        """Submit and return the raw engine event queue for bridge-pumped
        streaming (server/stream_bridge.py) — one pump thread serves
        every stream instead of a parked thread per stream. None for
        the non-engine paths (mamba / unloaded), which stream via the
        plain generator."""
        if self.engine is None or self.mamba is not None \
                or self.rwkv is not None:
            return None
        return self.engine.submit(self._to_request(opts))

    def predict_stream(self, opts: PredictOptions) -> Iterator[Reply]:
        if self.mamba is not None or self.rwkv is not None:
            # the recurrent generate is one device dispatch; stream the
            # text then the final (the reference's HF path has the same
            # whole-reply granularity for SSM models)
            r = self._recurrent_reply(opts)
            if r.message and not r.error:
                yield Reply(message=r.message)
            yield r
            return
        if self.engine is None:
            yield Reply(error="model not loaded")
            return
        q = self.engine.submit(self._to_request(opts))
        while True:
            ev: StreamEvent = q.get()
            if ev.done:
                yield _final_reply(ev)
                return
            if ev.text:
                yield Reply(message=ev.text, token_id=ev.token_id)

    def tokenize_string(self, opts: PredictOptions) -> TokenizationResponse:
        if self.tokenizer is None:
            return TokenizationResponse()
        ids = self.tokenizer.encode(opts.prompt)
        return TokenizationResponse(length=len(ids), tokens=ids)

    def embedding(self, opts: PredictOptions) -> EmbeddingResult:
        if self.engine is None:
            raise RuntimeError("model not loaded")
        text = opts.embeddings or opts.prompt
        vec = self.engine.embed(text)
        return EmbeddingResult(embeddings=[float(x) for x in vec])

    def apply_lora(self, adapter_dir: str, scale: float = 1.0) -> int:
        """Hot-apply a LoRA adapter to the RUNNING engine (ref: llama.cpp
        LoRA hot-apply). Weight swap only — no recompilation; in-flight
        scans finish on the old weights, the next dispatch uses the new."""
        if self.engine is None or self.spec is None:
            raise RuntimeError("model not loaded")
        if getattr(self, "_quantized", False):
            raise RuntimeError(
                "LoRA hot-apply needs full-precision weights; load the "
                "model without quantization (or restart with the adapter "
                "in lora_adapters, which merges before quantizing)")
        self._pager_prepare_swap()
        params, n = merge_lora(self.spec, self.engine.params, adapter_dir,
                               scale=scale)
        self.engine.params = self._reshard(params)
        self._pager_after_swap()
        return n

    def remove_lora(self, adapter_dir: str, scale: float = 1.0) -> int:
        """Hot-unmerge a previously applied adapter (same scale)."""
        if self.engine is None or self.spec is None:
            raise RuntimeError("model not loaded")
        if self._quantized:
            raise RuntimeError(
                "LoRA hot-unmerge needs full-precision weights")
        self._pager_prepare_swap()
        params, n = merge_lora(self.spec, self.engine.params, adapter_dir,
                               scale=scale, sign=-1.0)
        self.engine.params = self._reshard(params)
        self._pager_after_swap()
        return n

    def _pager_prepare_swap(self) -> None:
        """A LoRA hot-apply reassigns engine.params: the tree must be
        device-resident first (merge reads it), and the pager's host
        mirror goes stale the moment the swap lands."""
        pager = getattr(self.engine, "_pager", None)
        if pager is not None and not pager.ensure_hot():
            raise RuntimeError(
                "weights not device-resident (promotion timed out); "
                "retry the LoRA operation")

    def _pager_after_swap(self) -> None:
        pager = getattr(self.engine, "_pager", None)
        if pager is not None:
            pager.invalidate_host()

    def _reshard(self, params):
        """merge_lora round-trips leaves through host memory; under a mesh
        the merged leaves must go back to their NamedShardings or XLA
        replicates them on every chip."""
        if self.engine is not None and self.engine.mesh is not None:
            from ..parallel.sharding import shard_params

            return shard_params(params, self.engine.mesh)
        return params

    def get_metrics(self) -> MetricsResponse:
        if self.engine is None:
            return MetricsResponse()
        m = self.engine.metrics
        return MetricsResponse(
            tokens_per_second=m.tokens_per_second,
            tokens_generated=m.tokens_generated,
            prompt_tokens_processed=m.prompt_tokens_processed,
        )

    def engine_stats(self) -> Optional[dict]:
        """Live serving-state snapshot for /backend/monitor — host-held
        scheduler values only (no device sync rides a monitor poll)."""
        eng = self.engine
        if eng is None:
            return None
        m = eng.metrics
        with eng._lock:
            queue_depth = len(eng._pending)
        busy = sum(1 for s in eng.slots if s.active)
        used = sum(s.n_past for s in eng.slots if s.active)
        resident = sum(len(s.cache_tokens) for s in eng.slots)
        reused, filled = m.prefix_reused_tokens, m.prefill_tokens
        return {
            # the device and the attention route this engine chose at
            # construction, as JAX reported them then
            "platform": eng.platform,
            "device_kind": eng.device_kind,
            "paged": bool(eng._paged),
            "attention_path": eng.attention_path,
            # grouped_kernel | ragged_dot; None without experts
            "expert_path": eng.expert_path,
            # "" on the kernel route, else the condition that ruled the
            # Pallas kernel out
            "kernel_ineligible": eng.kernel_ineligible,
            # the layers by kind, the bytes of each cache, and the
            # paths refused for a model with recurrent state (by path:
            # the reason, naming the type)
            "layer_kinds": eng.layer_kinds(),
            "cache_bytes": eng.cache_bytes(),
            # what ONE cached token holds over all layers, as stored
            "cache_bytes_per_token": eng.kv_row_bytes,
            # the published ids of the experts a layer holds here
            # ([first, last]; a share when fewer than the router scores)
            "experts_held": eng.experts_held(),
            "state_refusals": dict(eng.state_refusals),
            "warmup_variants": eng.warmup_variants,
            "n_slots": eng.n_slots,
            "slots_busy": busy,
            "queue_depth": queue_depth,
            "kv_slot_utilization": round(
                used / float(eng.n_slots * eng.max_seq), 4),
            "kv_resident_prefix_tokens": resident,
            "tokens_per_second": round(m.tokens_per_second, 2),
            "tokens_generated": m.tokens_generated,
            "prompt_tokens_processed": m.prompt_tokens_processed,
            "requests_completed": m.requests_completed,
            "spec_tokens": m.spec_tokens,
            "prefix_cache": {
                "reused_tokens": reused,
                "prefilled_tokens": filled,
                "copies": m.prefix_copies,
                "hit_rate": round(reused / max(reused + filled, 1), 4),
            },
            # device observability: cost-model MFU/roofline summary and
            # HBM ledger snapshot (None when the knobs are off) — still
            # host-held values only
            "costmodel": eng.cost_stats(),
            "hbm": eng.hbm_stats(),
            # the last 32 program loads, each with its full variant key
            # (kind, key, source, seconds, in_warmup), and the
            # scheduler thread's self time per phase
            "program_loads": eng._loads.stats(),
            "sched_phase_seconds": {
                ph: round(v, 4)
                for ph, v in eng._phases.totals.items()},
        }


def _final_reply(ev: StreamEvent) -> Reply:
    return Reply(
        message=ev.full_text,
        tokens=ev.completion_tokens,
        prompt_tokens=ev.prompt_tokens,
        timing_prompt_processing=ev.timing_prompt_processing_ms,
        timing_token_generation=ev.timing_token_generation_ms,
        timing_queue=ev.timing_queue_ms,
        timing_first_token=ev.timing_first_token_ms,
        finish_reason=ev.finish_reason,
        error=ev.error,
        retry_after_s=ev.retry_after_s,
    )
