"""REAL two-process multihost validation: leader + follower in separate
jax.distributed processes on CPU (gloo collectives), exercising the
actual JaxBroadcastChannel transport — not the in-process LocalChannel.

The reference has no automated multi-node tests at all (SURVEY.md §4);
this is the "multi-host sim via jax.distributed on CPU" it calls for.
Each process runs the identical engine; the leader serves requests and
publishes dispatch records over broadcast_one_to_all, the follower
replays them, and both print a digest of their final KV cache — which
must match bitwise."""

import os
import subprocess
import sys

_WORKER = r"""
import hashlib, os, sys
pid = int(sys.argv[1])
port = sys.argv[2]
import jax
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(f"127.0.0.1:{port}", num_processes=2,
                           process_id=pid)
jax.config.update("jax_default_matmul_precision", "highest")
import jax.numpy as jnp
import numpy as np
from localai_tfp_tpu.engine.engine import GenRequest, LLMEngine
from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
from localai_tfp_tpu.models.llm_spec import tiny_spec
from localai_tfp_tpu.models.transformer import init_params
from localai_tfp_tpu.parallel import multihost

tk = ByteTokenizer()
spec = tiny_spec(vocab_size=tk.vocab_size, max_position=512)
params = init_params(jax.random.PRNGKey(0), spec, dtype=jnp.float32)
channel = multihost.JaxBroadcastChannel()
kw = dict(n_slots=2, max_seq=128, prefill_buckets=(8, 32),
          cache_dtype=jnp.float32, decode_steps=4)

if pid == 0:
    eng = LLMEngine(spec, params, tk, channel=channel, **kw)
    reqs = [
        GenRequest(prompt_ids=tk.encode("two proc hello"), max_tokens=5,
                   ignore_eos=True),
        GenRequest(prompt_ids=tk.encode("abc"), max_tokens=5,
                   temperature=0.7, seed=9, ignore_eos=True),
    ]
    texts = []
    for q in eng.submit_many(reqs):
        while True:
            ev = q.get(timeout=120)
            if ev.done:
                texts.append(ev.full_text)
                break
    eng.close()
    channel.publish("stop", None)
    assert all(t is not None for t in texts)
else:
    eng = LLMEngine(spec, params, tk, follower=True, **kw)
    multihost.run_follower_engine(eng, channel)

digest = hashlib.sha256(
    np.ascontiguousarray(np.asarray(eng.cache.k)).tobytes()
    + np.ascontiguousarray(np.asarray(eng.cache.v)).tobytes()
).hexdigest()
print(f"DIGEST {pid} {digest}", flush=True)
"""


def test_two_process_leader_follower_bitwise_identical(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    # a clean env: the children run on CPU (two processes cannot share
    # a chip), and PYTHONPATH must point at the repo only
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo
    import socket

    with socket.socket() as s:  # ephemeral port: concurrent runs must
        s.bind(("127.0.0.1", 0))  # not collide on a fixed coordinator
        port = str(s.getsockname()[1])
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(i), port],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
    ) for i in range(2)]
    digests = {}
    logs = []
    for i, p in enumerate(procs):
        out, _ = p.communicate(timeout=540)
        text = out.decode()
        logs.append(text)
        assert p.returncode == 0, f"proc {i} failed:\n{text[-3000:]}"
        for line in text.splitlines():
            if line.startswith("DIGEST"):
                _, pid, digest = line.split()
                digests[int(pid)] = digest
    assert set(digests) == {0, 1}, logs
    assert digests[0] == digests[1], (
        "leader and follower KV caches diverged", logs)
