#!/usr/bin/env python3
"""First contact: the engine server's normal path on a TPU, proved end to end.

    python chip_smoke.py            # one chip: device, kernels, serve, reference
    python chip_smoke.py --chips 4  # four one-chip replicas behind the router

Run it on a machine with the chip(s). It fails where JAX finds no TPU; a
pass is the last line of standard output,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
and exit code 0. Earlier lines say what each phase did: seconds, programs
compiled and the seconds that took (set-up, not speed), engine counters,
peak device memory, compile-cache files. Nothing here is a measurement.

A chip belongs to one process at a time, so this process never
initialises a JAX backend: each phase is its single chip-holding child.

* ``device``    — what JAX sees; fails unless it is a TPU.
* ``kernels``   — every Pallas kernel the serving path can reach, once, at
  published widths on seeded inputs that mix prefill-chunk rows with
  decode rows, against its XLA reference.
* ``serve``     — ``python -m rbg_tpu.engine.server`` as a deployment starts
  it (llama3-1b, nothing cut, defaults for kernel and ragged dispatch),
  spoken to over the TCP wire: warmup, a short greedy request, eight
  concurrent requests whose steps mix prefill and decode, a streamed one.
* ``reference`` — the same parameters (same seed) through the plain dense
  ``forward``: the served tokens and their log-probabilities must agree,
  and the engine's compiled step programs must hold the Pallas kernel.
* ``replicas``  — ``--chips 4`` only, and then the only phase after
  ``device``: four servers pinned to a chip each behind
  ``rbg_tpu.engine.router``; the router's answers must equal replica 0's.

``--rehearse`` walks the same control flow on the CPU at the ``tiny``
preset with kernels in interpret mode, to find faults before chip time is
spent. It proves nothing about the chip and always ends ``"ok": false``
with a non-zero exit code.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
import traceback

from rbg_tpu.utils import chipenv          # imports no JAX

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

# The deployment the serve phase starts: every layer and width of
# llama3-1b as published (16 layers, d 2048, 32/8 x 64, vocabulary
# 128 256). 6144 pages of 16 tokens are 3 GiB of bf16 KV; during a step
# the chip holds about three times that, because the 64-wide head pads to
# a 128-lane tile in the layout the kernel reads and the step program
# keeps a padded copy of both pools (what the compiler reports for this
# program is pinned by tests/test_chip_compile.py).
SERVE_CONFIG = dict(model="llama3-1b", page_size=16, num_pages=6144,
                    max_batch=8, max_seq_len=8192, prefill_chunk=256)
REHEARSE_CONFIG = dict(model="tiny", page_size=16, num_pages=256,
                       max_batch=4, max_seq_len=256, prefill_chunk=32)

# Stated tolerances. The kernels run as the engine runs them, at Mosaic's
# default matmul precision (float32 operands go through the MXU in bf16
# passes), against an XLA reference at full precision; both round the
# result to bf16. Two bf16 steps at the largest outputs these inputs give
# (2^-6 each in [2, 4)): one for the operand rounding, one for the result.
KERNEL_ATOL = 2.0 ** -5
# Log-probability of a greedy token, served vs dense (or one batch shape
# vs another): bf16 weights and activations through 16 layers, paged
# attention against contiguous, logits of standard deviation near 1.
LOGPROB_ATOL = 0.15

GQA_WIDTHS = {"llama3-1b": (32, 8, 64), "llama3-8b": (32, 8, 128),
              "qwen2-0.5b": (14, 2, 64)}
MLA_WIDTH = (16, 512, 64)       # deepseek-v2-lite: heads, kv_lora_rank, rope


def say(msg: str) -> None:
    print(msg, flush=True)


def _config(args) -> dict:
    return REHEARSE_CONFIG if args.rehearse else SERVE_CONFIG


# ---------------------------------------------------------------------------
# children: each is the one process holding the chip while it runs
# ---------------------------------------------------------------------------


def _finish_child(result_path: str, counter, **fields) -> None:
    """Common tail of a chip-holding phase: its set-up cost and device
    memory into the result file the parent reads and prints."""
    out = {"compile": counter.snapshot(),
           "memory": chipenv.memory_summary(), **fields}
    with open(result_path, "w") as f:
        json.dump(out, f)


def child_device(args) -> None:
    dev = chipenv.device_summary()
    say("device: platform={platform} kind={kind!r} count={count} "
        "hbm_bytes={hbm}".format(hbm=dev.get("hbm_bytes"), **dev))
    with open(args.result, "w") as f:
        json.dump(dev, f)


def _mixed_pack(rng, num_rows, pages_per_row, page, total_tokens):
    """Seeded rows of one mixed step: three prefill chunks (one resuming
    mid-prompt, one from position 0, one ending off a page boundary) and
    decode rows from a one-token cache to a full table line, packed
    row-major and padded to ``total_tokens`` under the pack's pad
    contract (row 0, position -1)."""
    import numpy as np
    cap = pages_per_row * page
    chunks = [(total_tokens * 3 // 8, cap // 2), (total_tokens // 4, 0),
              (total_tokens // 5 + 1, cap // 4 + 3)]      # (q_len, start)
    decode_lens = [cap - 5, 1, page + 1, cap // 3, cap]
    kv_lens, row_ids, pos = [], [], []
    for r in range(num_rows):
        if r < len(chunks):
            n, start = chunks[r]
            kv_lens.append(start + n)
            pos.extend(range(start, start + n))
        else:
            n = 1
            kv_lens.append(decode_lens[(r - len(chunks)) % len(decode_lens)])
            pos.append(kv_lens[-1] - 1)
        row_ids.extend([r] * n)
    real = len(pos)
    if real > total_tokens or max(kv_lens) > cap:
        raise ValueError(f"pack of {real} tokens does not fit")
    row_ids += [0] * (total_tokens - real)
    pos += [-1] * (total_tokens - real)
    # Every row owns distinct physical pages, shuffled over the pool.
    table = rng.permutation(num_rows * pages_per_row).reshape(
        num_rows, pages_per_row) + 1
    return (np.asarray(kv_lens, np.int32), np.asarray(row_ids, np.int32),
            np.asarray(pos, np.int32)[None], table.astype(np.int32), real)


def _long_table_pack(rng, page, pages_per_row, short, long_, total_tokens):
    """Two short rows under a table ``pages_per_row`` wide, as a server
    with a large ``max_seq_len`` sees them: a decode token on a cache of
    ``short`` slots and an eight-token chunk that ends at ``long_``. The
    table's dead entries all name the last page of the pool."""
    import numpy as np
    kv_lens = [short, long_]
    row_ids = [0] + [1] * 8
    pos = [short - 1] + list(range(long_ - 8, long_))
    real = len(pos)
    row_ids += [0] * (total_tokens - real)
    pos += [-1] * (total_tokens - real)
    table = np.full((2, pages_per_row), 2 * pages_per_row, np.int64)
    live = rng.permutation(2 * pages_per_row) + 1
    for r, n in enumerate(kv_lens):
        n_pages = -(-n // page)
        table[r, :n_pages] = live[r * pages_per_row:][:n_pages]
    return (np.asarray(kv_lens, np.int32), np.asarray(row_ids, np.int32),
            np.asarray(pos, np.int32)[None], table.astype(np.int32), real)


def child_kernels(args) -> None:
    """Each kernel family on the chip against its XLA reference: on one
    mixed step's rows, and on two short rows under a long table."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    cache_dir = chipenv.configure_compile_cache()
    counter = chipenv.CompileCounter().install()
    files0 = chipenv.cache_files(cache_dir)

    from rbg_tpu.ops.mla_attention import (paged_mla_attention_xla,
                                           ragged_paged_mla_attention_xla)
    from rbg_tpu.ops.paged_attention import paged_attention_xla, quantize_kv
    from rbg_tpu.ops.pallas import paged_attention_kernel as K
    from rbg_tpu.ops.pallas import ragged_attention_kernel as RK
    from rbg_tpu.ops.ragged_paged_attention import ragged_paged_attention_xla

    interpret = args.rehearse
    if args.rehearse:
        gqa, mla = {"tiny": (4, 2, 32)}, (4, 64, 16)
        R, P, page, T = 4, 4, 16, 32
        long_table = (16, 17, 40)       # table width, the two rows' lengths
    else:
        gqa, mla = GQA_WIDTHS, MLA_WIDTH
        R, P, page, T = 8, 64, 16, 256
        long_table = (512, 17, 900)
    rng = np.random.default_rng(args.seed)
    packs = {"": _mixed_pack(rng, R, P, page, T),
             f" P={long_table[0]}": _long_table_pack(rng, page, *long_table,
                                                     total_tokens=16)}
    worst = {label: 0.0 for label in packs}

    def normal(shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32),
                           jnp.bfloat16)

    def check(kernel, reference, label, case, args, rows):
        """One kernel as the engine calls it, against its XLA reference
        on the same arguments. The reference runs at full matmul
        precision: XLA's default on a TPU rounds float32 operands to
        bf16, which would be the larger error of the two."""
        name = f"{kernel.__name__}{label}{case}"
        got = np.asarray(kernel(*args, interpret=interpret), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(reference(*args), np.float32)
        if not np.isfinite(got[rows]).all():
            raise SystemExit(f"kernel {name}: non-finite output")
        err = float(np.max(np.abs(got[rows] - want[rows])))
        worst[case] = max(worst[case], err)
        say(f"kernel {name}: max_abs_err={err:.4g} (atol {KERNEL_ATOL:.4g})")
        if err > KERNEL_ATOL:
            raise SystemExit(f"kernel {name}: error {err} over {KERNEL_ATOL}")

    for case, (kv_lens, row_ids, qpos, table, real) in packs.items():
        rows, tokens = kv_lens.shape[0], row_ids.shape[0]
        NP = int(table.max()) + 1
        dec_pos = (kv_lens - 1)[:, None]
        packed, every = (0, slice(0, real)), slice(None)   # rows compared
        for width, (H, KV, hd) in gqa.items():
            q_rag, q_dec = normal((1, tokens, H, hd)), normal((rows, 1, H, hd))
            k, v = normal((NP, page, KV, hd)), normal((NP, page, KV, hd))
            (kq, ks), (vq, vs) = quantize_kv(k), quantize_kv(v)
            rag = (table, qpos, kv_lens, row_ids)
            dec = (table, dec_pos, kv_lens)
            label = f"[{width}]"
            check(RK.ragged_paged_attention_pallas, ragged_paged_attention_xla,
                  label, case, (q_rag, k, v, *rag), packed)
            check(RK.ragged_paged_attention_pallas_q,
                  ragged_paged_attention_xla,
                  label, case, (q_rag, kq, vq, *rag, ks, vs), packed)
            check(K.paged_attention_pallas, paged_attention_xla,
                  label, case, (q_dec, k, v, *dec), every)
            check(K.paged_attention_pallas_q, paged_attention_xla,
                  label, case, (q_dec, kq, vq, *dec, ks, vs), every)

        H, dc, dr = mla
        scale = (dc // 4 + dr) ** -0.5    # deepseek: 128 nope + 64 rope
        ql_rag, qp_rag = normal((1, tokens, H, dc)), normal((1, tokens, H, dr))
        ql_dec, qp_dec = normal((rows, 1, H, dc)), normal((rows, 1, H, dr))
        c, pe = normal((NP, page, 1, dc)), normal((NP, page, 1, dr))
        (cq, cs), (pq, ps) = quantize_kv(c), quantize_kv(pe)
        rag = (table, qpos, kv_lens, row_ids, scale)
        dec = (table, dec_pos, kv_lens, scale)
        check(RK.ragged_paged_mla_attention_pallas,
              ragged_paged_mla_attention_xla,
              "", case, (ql_rag, qp_rag, c, pe, *rag), packed)
        check(RK.ragged_paged_mla_attention_pallas_q,
              ragged_paged_mla_attention_xla,
              "", case, (ql_rag, qp_rag, cq, pq, *rag, cs, ps), packed)
        check(K.paged_mla_attention_pallas, paged_mla_attention_xla,
              "", case, (ql_dec, qp_dec, c, pe, *dec), every)
        check(K.paged_mla_attention_pallas_q, paged_mla_attention_xla,
              "", case, (ql_dec, qp_dec, cq, pq, *dec, cs, ps), every)

    say("kernels: largest error " + ", ".join(
        f"{err:.4g} ({case.strip() or 'mixed step'})"
        for case, err in worst.items()))
    _finish_child(args.result, counter, worst_abs_err=max(worst.values()),
                  cache_dir=cache_dir, cache_files_before=files0,
                  cache_files_after=chipenv.cache_files(cache_dir))


def _kernels_in(compiled) -> list:
    """Names of the Pallas calls inside a compiled program, read off the
    ``tpu_custom_call`` lines of its HLO."""
    import re
    names = []
    for line in compiled.as_text().splitlines():
        if "tpu_custom_call" in line:
            m = re.search(r'op_name="[^"]*?jit\((\w+)\)/pallas_call', line)
            names.append(m.group(1) if m else "pallas_call")
    return names


def child_reference(args) -> None:
    """Same seed, same parameters, no paging and no kernel: the plain
    dense ``forward`` must agree with what the server answered; and the
    engine's own step programs, compiled as the server compiled them, must
    hold the Pallas kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    cache_dir = chipenv.configure_compile_cache()
    counter = chipenv.CompileCounter().install()
    files0 = chipenv.cache_files(cache_dir)

    from rbg_tpu.engine import Engine, EngineConfig
    from rbg_tpu.engine.sampler import row_keys
    from rbg_tpu.models import KVCache, forward

    with open(args.record) as f:
        record = json.load(f)
    cfg = EngineConfig(**_config(args))
    eng = Engine(cfg)
    mcfg = eng.mcfg

    prompt, served = record["prompt"], record["tokens"]
    seq = jnp.asarray([prompt + served], jnp.int32)
    logits, _ = jax.jit(lambda p, t, c: forward(p, mcfg, t, c))(
        eng.params, seq, KVCache.create(mcfg, 1, seq.shape[1]))
    logits = np.asarray(logits[0], np.float32)
    if logits.shape != (len(prompt) + len(served), mcfg.vocab_size) \
            or not np.isfinite(logits).all():
        raise SystemExit(f"dense forward: bad logits {logits.shape}")
    # Row i predicts token i+1: the rows that predicted the served tokens.
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(served)]
    logp = rows - np.logaddexp.reduce(rows, axis=-1, keepdims=True)
    dense_lp = logp[np.arange(len(served)), served]
    err = np.abs(dense_lp - np.asarray(record["logprobs"], np.float32))
    # A served greedy token must be the dense argmax, or so near it that
    # two correct bf16 programs may rank the pair either way.
    regret = logp.max(axis=-1) - dense_lp
    agree = int((regret == 0).sum())
    say(f"reference: dense forward vs served, {len(served)} greedy tokens "
        f"after a {len(prompt)}-token prefill: logprob max_abs_err="
        f"{err.max():.4g}, first token (prefill logits) err={err[0]:.4g}, "
        f"served token is the dense argmax on {agree}/{len(served)}, "
        f"largest shortfall {regret.max():.4g} (atol {LOGPROB_ATOL}; "
        f"dense logprobs span {logp.max() - logp.min():.3g})")
    if err.max() > LOGPROB_ATOL:
        raise SystemExit(f"served logprobs off the dense reference: {err}")
    if regret.max() > LOGPROB_ATOL:
        raise SystemExit("served greedy tokens are not the dense "
                         f"reference's: shortfall {regret}")

    # The step programs the server ran, by the engine's own builders at
    # the shapes its warmup compiled (cache hits where the cache is warm).
    S = jax.ShapeDtypeStruct
    i32 = jnp.int32
    R, P = cfg.max_batch, cfg.max_pages_per_seq
    T = R * cfg.prefill_chunk
    unified = eng._get_ragged_fn(R, T).lower(
        eng.params, S((1, T), i32), S((1, T), i32), S((1, T), bool),
        S((T,), i32), S((R,), i32), S((R, P), i32), eng.cache.k_pages,
        eng.cache.v_pages, None, None, S((1, T), i32),
        S((eng._rows_max,), i32), S((eng._rows_max,), i32)).compile()
    temps, ks, tps, mps, seeds, rids, _, _, _ = eng._sampling_rows([], R)
    vec = S((R,), i32)
    decode = eng._get_decode_fn(R, False, False).lower(
        eng.params, vec, vec, vec, S((R, P), i32),
        S((R, cfg.multi_step), bool), vec, eng.cache.k_pages,
        eng.cache.v_pages, None, None,
        row_keys(seeds, eng._sample_base, rids), jnp.asarray(temps),
        jnp.asarray(ks), jnp.asarray(tps), jnp.asarray(mps)).compile()
    programs = {}
    for name, shape, compiled in (
            ("unified_step", f"{R} rows, {T} packed tokens", unified),
            ("fused_decode", f"{R} rows, window {cfg.multi_step}", decode)):
        kernels = programs[name] = _kernels_in(compiled)
        say(f"program {name} ({shape}): attention = "
            f"{', '.join(sorted(set(kernels))) or 'XLA'} "
            f"({len(kernels)} tpu_custom_call)")
        if not kernels and not args.rehearse:
            raise SystemExit(f"{name} holds no tpu_custom_call: the chip "
                             "is not running the Pallas kernel")

    _finish_child(args.result, counter,
                  logprob_max_abs_err=float(err.max()),
                  argmax_agree=agree, programs=programs,
                  cache_dir=cache_dir, cache_files_before=files0,
                  cache_files_after=chipenv.cache_files(cache_dir))


# ---------------------------------------------------------------------------
# parent: starts children, speaks the wire, never touches a JAX backend
# ---------------------------------------------------------------------------


class Children:
    """Every process this script starts, so that all of them are stopped
    whatever happens."""

    def __init__(self):
        self.procs = []

    def spawn(self, argv, env, log_name):
        log = open(os.path.join(OUT_DIR, log_name), "w")
        proc = subprocess.Popen(argv, env=env, cwd=REPO, stdout=log,
                                stderr=subprocess.STDOUT)
        proc.log_path = log.name
        log.close()
        self.procs.append(proc)
        return proc

    def stop_all(self):
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def _tail(path: str, lines: int = 40) -> str:
    with open(path, errors="replace") as f:
        return "".join(f.readlines()[-lines:])


def run_child_phase(phase: str, args, extra=()) -> dict:
    """One chip-holding child of this script; its lines go straight to our
    standard output. Raises unless it exits 0 and left its result."""
    result = os.path.join(OUT_DIR, f"{phase}.json")
    if os.path.exists(result):
        os.remove(result)
    argv = [sys.executable, os.path.abspath(__file__), "--phase", phase,
            "--result", result, "--seed", str(args.seed), *extra]
    if args.rehearse:
        argv.append("--rehearse")
    t0 = time.time()
    rc = subprocess.run(argv, cwd=REPO).returncode
    if rc != 0:
        raise RuntimeError(f"phase {phase} failed: exit code {rc}")
    with open(result) as f:
        out = json.load(f)
    say(f"phase {phase}: ok wall_s={time.time() - t0:.1f} "
        + json.dumps({k: out[k] for k in ("compile", "memory",
                                          "cache_files_before",
                                          "cache_files_after") if k in out}))
    return out


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _request(addr, obj, timeout):
    from rbg_tpu.engine.protocol import request_once
    reply, _, _ = request_once(addr, obj, timeout=timeout)
    if not reply or reply.get("error"):
        raise RuntimeError(f"{obj['op']} to {addr} failed: {reply}")
    return reply


def _wait_healthy(addr, proc, timeout):
    from rbg_tpu.engine.protocol import request_once
    deadline = time.monotonic() + timeout
    while True:
        if proc.poll() is not None:
            raise RuntimeError(
                f"server on {addr} exited {proc.returncode} before it was "
                f"ready:\n{_tail(proc.log_path)}")
        try:
            h, _, _ = request_once(addr, {"op": "health"}, timeout=5)
            if h and h.get("ok"):
                return h
        except OSError:
            pass
        if time.monotonic() > deadline:
            raise RuntimeError(f"server on {addr} not ready in {timeout}s:\n"
                               f"{_tail(proc.log_path)}")
        time.sleep(1.0)


def _server_argv(config: dict, port: int) -> list:
    """The engine server as a deployment starts it; kernel and ragged
    dispatch are left at their defaults (auto)."""
    return [sys.executable, "-m", "rbg_tpu.engine.server",
            "--port", str(port), "--model", config["model"],
            "--page-size", str(config["page_size"]),
            "--num-pages", str(config["num_pages"]),
            "--max-batch", str(config["max_batch"]),
            "--max-seq-len", str(config["max_seq_len"]),
            "--prefill-chunk", str(config["prefill_chunk"])]


def _server_env(base: dict) -> dict:
    # The executor's port contract must not reach a server started here.
    return {k: v for k, v in base.items()
            if k not in ("RBG_SERVE_PORT", "RBG_PORT_SERVE", "RBG_DATA_TOKEN")}


def _prompt(rng: random.Random, n: int, vocab: int) -> list:
    return [rng.randrange(1, vocab) for _ in range(n)]


def _generate_all(addr, prompts, max_new, timeout, logprobs=False):
    """Send ``prompts`` concurrently; every one must come back whole.
    Returns the replies (``tokens``, and ``logprobs`` when asked for)."""
    replies = [None] * len(prompts)
    errors = []

    def one(i):
        try:
            replies[i] = _request(addr, {"op": "generate",
                                         "prompt": prompts[i],
                                         "max_new_tokens": max_new,
                                         "logprobs": logprobs}, timeout)
        except Exception as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout + 30)
    if errors or any(t.is_alive() for t in threads):
        raise RuntimeError(f"concurrent requests failed: {errors}")
    for i, r in enumerate(replies):
        if len(r["tokens"]) != max_new:
            raise RuntimeError(f"request {i}: {len(r['tokens'])} tokens, "
                               f"wanted {max_new}")
    return replies


def _same_answer(a: dict, b: dict) -> tuple:
    """Compare two greedy answers to one prompt from two correct bf16
    programs (another batch shape, a prefix served from cache). Returns
    (verdict, largest log-probability gap seen). Where the tokens agree
    their log-probabilities must too. "equal": token for token. "tie":
    they part at a step where both runs' best log-probabilities lie
    within the tolerance — either token was a valid argmax, and from
    there on the contexts differ, so the comparison stops. Anything else
    is "differ"."""
    n = next((i for i, (x, y) in enumerate(zip(a["tokens"], b["tokens"]))
              if x != y), len(a["tokens"]))
    upto = min(n + 1, len(a["tokens"]))
    gap = max(abs(x - y) for x, y in zip(a["logprobs"][:upto],
                                         b["logprobs"][:upto]))
    if gap > LOGPROB_ATOL:
        return "differ", gap
    return ("equal" if n == len(a["tokens"]) else "tie"), gap


def _generate_streamed(addr, prompt, max_new, timeout):
    from rbg_tpu.engine.protocol import recv_msg, send_msg
    host, port = addr.rsplit(":", 1)
    tokens, frames = [], 0
    with socket.create_connection((host, int(port)), timeout=timeout) as s:
        send_msg(s, {"op": "generate", "prompt": prompt, "stream": True,
                     "max_new_tokens": max_new})
        while True:
            frame, _, _ = recv_msg(s)
            if frame is None or frame.get("error"):
                raise RuntimeError(f"stream failed: {frame}")
            tokens += frame["tokens"]
            frames += 1
            if frame["done"]:
                break
    if len(tokens) != max_new:
        raise RuntimeError(f"stream: {len(tokens)} tokens, wanted {max_new}")
    return tokens, frames


def phase_serve(args, children: Children) -> str:
    """Start the server, drive it over the wire, stop it with SIGTERM.
    Returns the path of the record the reference phase checks."""
    from rbg_tpu.models.config import get_config
    t0 = time.time()
    config = _config(args)
    vocab = get_config(config["model"]).vocab_size
    long_lo, long_hi = (20, 120) if args.rehearse else (100, 2000)
    rng = random.Random(args.seed)

    port = _free_port()
    addr = f"127.0.0.1:{port}"
    proc = children.spawn(_server_argv(config, port),
                          _server_env(dict(os.environ)), "server.log")
    health = _wait_healthy(addr, proc, timeout=600)
    dev = health["device"]
    say(f"serve: server ready in {time.time() - t0:.1f}s on device "
        f"platform={dev['platform']} kind={dev['kind']!r} id={dev['id']} "
        f"holds={dev['chip_files']}")
    if dev["platform"] != "tpu" and not args.rehearse:
        raise RuntimeError(f"server holds {dev['platform']}, not a TPU")

    t1 = time.time()
    _request(addr, {"op": "warmup"}, timeout=1000)
    warm = _request(addr, {"op": "metrics"}, 30)["metrics"]
    say(f"serve: warmup wall_s={time.time() - t1:.1f} "
        f"compile={json.dumps(warm['compile'])}")

    short = _prompt(rng, 24, vocab)
    reply = _request(addr, {"op": "generate", "prompt": short,
                            "max_new_tokens": 8, "logprobs": True}, 600)
    if len(reply["tokens"]) != 8 or len(reply["logprobs"]) != 8:
        raise RuntimeError(f"short request came back short: {reply}")
    record = os.path.join(OUT_DIR, "short_request.json")
    with open(record, "w") as f:
        json.dump({"prompt": short, "tokens": reply["tokens"],
                   "logprobs": reply["logprobs"]}, f)

    before = _request(addr, {"op": "metrics"}, 30)["metrics"]
    lengths = [rng.randint(long_lo, long_hi) for _ in range(8)]
    _generate_all(addr, [_prompt(rng, n, vocab) for n in lengths],
                  max_new=24, timeout=900)
    _, frames = _generate_streamed(
        addr, _prompt(rng, (long_lo + long_hi) // 4, vocab), 16, 600)
    after = _request(addr, {"op": "metrics"}, 30)["metrics"]

    moved = {k: after[k] - before[k]
             for k in ("unified_steps", "prefill_tokens", "decode_tokens")}
    say(f"serve: 8 concurrent prompts of {lengths} tokens and one streamed "
        f"request ({frames} frames) returned every token; counters moved "
        f"{json.dumps(moved)}; compile={json.dumps(after['compile'])} "
        f"device_memory={json.dumps(after['device_memory'])}")
    if min(moved.values()) <= 0:
        raise RuntimeError(f"engine counters did not all move: {moved}")
    if moved["prefill_tokens"] < sum(lengths):
        raise RuntimeError("fewer prompt tokens prefilled than were sent")

    proc.send_signal(signal.SIGTERM)
    rc = proc.wait(timeout=120)
    if rc != 0:
        raise RuntimeError(f"server exited {rc} after SIGTERM:\n"
                           f"{_tail(proc.log_path)}")
    say(f"phase serve: ok wall_s={time.time() - t0:.1f}")
    return record


def phase_replicas(args, children: Children, count: int) -> None:
    """``count`` one-chip servers behind the router; the router's answers
    must equal replica 0's own (every replica starts from the same seed,
    and greedy decoding of one prompt does not depend on its batch)."""
    from rbg_tpu.models.config import get_config
    t0 = time.time()
    config = _config(args)
    vocab = get_config(config["model"]).vocab_size
    rng = random.Random(args.seed)
    base = _server_env(dict(os.environ))

    ports = [_free_port() for _ in range(count)]
    addrs = [f"127.0.0.1:{p}" for p in ports]
    procs = [children.spawn(
        _server_argv(config, ports[i]),
        base if args.rehearse else chipenv.chip_env(i, count, base),
        f"replica{i}.log") for i in range(count)]
    devices = []
    for addr, proc in zip(addrs, procs):
        devices.append(_wait_healthy(addr, proc, timeout=600)["device"])
    # JAX numbers a pinned process's one device 0 on every chip: the
    # replicas are told apart by the chip device file each holds open,
    # all of them at once.
    say("replicas: " + "; ".join(
        f"{a} platform={d['platform']} jax_id={d['id']} count={d['count']} "
        f"holds={d['chip_files']}" for a, d in zip(addrs, devices)))
    if not args.rehearse:
        held = [f for d in devices for f in d["chip_files"]]
        if any(d["platform"] != "tpu" or d["count"] != 1
               or not d["chip_files"] for d in devices) \
                or len(set(held)) != len(held):
            raise RuntimeError("the replicas do not hold one TPU chip each, "
                               f"all distinct: {devices}")

    def warm(addr):
        _request(addr, {"op": "warmup"}, timeout=1000)
    warmers = [threading.Thread(target=warm, args=(a,)) for a in addrs]
    t1 = time.time()
    for t in warmers:
        t.start()
    for t in warmers:
        t.join()
    say(f"replicas: {count} warmups side by side wall_s="
        f"{time.time() - t1:.1f}")

    router_port = _free_port()
    router = f"127.0.0.1:{router_port}"
    # The router computes nothing: keep it off the chips altogether.
    rproc = children.spawn(
        [sys.executable, "-m", "rbg_tpu.engine.router", "--port",
         str(router_port), "--backends", json.dumps({"unified": addrs})],
        {**base, "JAX_PLATFORMS": "cpu"}, "router.log")
    _wait_healthy(router, rproc, timeout=120)

    lo, hi, shared = (16, 60, 40) if args.rehearse else (64, 1500, 1000)
    prefix = _prompt(rng, shared, vocab)
    prompts = [_prompt(rng, rng.randint(lo, hi), vocab) for _ in range(14)]
    prompts += [prefix + _prompt(rng, 24, vocab) for _ in range(2)]
    before = [_request(a, {"op": "metrics"}, 30)["metrics"] for a in addrs]
    routed = _generate_all(router, prompts, 16, 900, logprobs=True)
    after = [_request(a, {"op": "metrics"}, 30)["metrics"] for a in addrs]
    served = [a["joins"] - b["joins"] for a, b in zip(after, before)]
    say(f"replicas: 16 requests through the router, two sharing a "
        f"{shared}-token prefix; requests joined per replica = {served}")
    if min(served) < 1:
        raise RuntimeError(f"a replica served nothing: {served}")
    direct = _generate_all(addrs[0], prompts, 16, 900, logprobs=True)
    verdicts, gaps = zip(*(_same_answer(r, d)
                           for r, d in zip(routed, direct)))
    say(f"replicas: router answers vs replica 0's own: "
        f"{verdicts.count('equal')}/16 equal token for token, "
        f"{verdicts.count('tie')} part at a near-tie, "
        f"{verdicts.count('differ')} differ; largest logprob gap over the "
        f"compared tokens {max(gaps):.4g} (atol {LOGPROB_ATOL})")
    if "differ" in verdicts or "equal" not in verdicts:
        raise RuntimeError("the router's answers differ from replica 0's: "
                           f"{verdicts}")
    for a, m in zip(addrs, after):
        say(f"replica {a}: compile={json.dumps(m['compile'])} "
            f"device_memory={json.dumps(m['device_memory'])}")
    say(f"phase replicas: ok wall_s={time.time() - t0:.1f}")


def run_phases(args, children: Children, device: dict) -> None:
    os.makedirs(OUT_DIR, exist_ok=True)
    dev = run_child_phase("device", args)
    device.update(platform=dev["platform"], kind=dev["kind"],
                  count=dev["count"])
    if dev["platform"] != "tpu" and not args.rehearse:
        raise RuntimeError(f"no TPU: JAX reports platform "
                           f"{dev['platform']!r}")
    if dev["count"] != args.chips and not args.rehearse:
        raise RuntimeError(f"--chips {args.chips} but JAX sees "
                           f"{dev['count']} device(s)")
    if args.chips > 1:
        phase_replicas(args, children, args.chips)
        return
    run_child_phase("kernels", args)
    record = phase_serve(args, children)
    run_child_phase("reference", args, extra=("--record", record))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = only the four-replica path behind the router")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds kernel inputs and every prompt")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU walk-through at the tiny preset; never a pass")
    ap.add_argument("--phase", help=argparse.SUPPRESS)
    ap.add_argument("--result", help=argparse.SUPPRESS)
    ap.add_argument("--record", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.phase:      # a chip-holding child of the process below
        {"device": child_device, "kernels": child_kernels,
         "reference": child_reference}[args.phase](args)
        return 0

    device = {"platform": None, "kind": None, "count": 0}
    children = Children()
    ok = False
    try:
        run_phases(args, children, device)
        ok = not args.rehearse
    except Exception:  # noqa: BLE001 — reported, then the run fails
        traceback.print_exc()
    finally:
        children.stop_all()
    sys.stderr.flush()
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
