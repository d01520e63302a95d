"""MoE model family: routing exactness, serving-path integration, expert
parallelism over the ep mesh axis."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rbg_tpu.models import KVCache, forward, get_config, init_params
from rbg_tpu.models.llama import forward_train
from rbg_tpu.models.training import train_n_steps
from rbg_tpu.parallel import make_mesh, param_specs, shard_pytree


@pytest.fixture(scope="module")
def moe_setup():
    cfg = get_config("tiny-moe")
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


def test_moe_forward_shapes_and_cache_path(moe_setup):
    cfg, params = moe_setup
    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab_size)
    logits, cache = forward(params, cfg, tokens, KVCache.create(cfg, 2, 16))
    assert logits.shape == (2, 8, cfg.vocab_size)
    assert forward_train(params, cfg, tokens).shape == (2, 8, cfg.vocab_size)


def test_moe_routing_matches_manual_reference(moe_setup):
    """The einsum dense-dispatch must equal a per-token python loop over the
    selected experts."""
    cfg, params = moe_setup
    from rbg_tpu.models.llama import _moe_mlp

    blk0 = jax.tree_util.tree_map(lambda x: x[0], params["blocks"])
    x = jax.random.normal(jax.random.key(2), (1, 5, cfg.hidden_size), jnp.float32)
    got = np.asarray(_moe_mlp(cfg, blk0, x))

    xn = np.asarray(x)
    router = np.asarray(blk0["router"], np.float64)
    want = np.zeros_like(got, dtype=np.float64)
    for t in range(5):
        xv = xn[0, t]
        logits = xv @ router
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(probs)[::-1][: cfg.experts_per_token]
        w = probs[top] / probs[top].sum()
        for wi, e in zip(w, top):
            g = xv @ np.asarray(blk0["moe_gate"])[e]
            u = xv @ np.asarray(blk0["moe_up"])[e]
            silu = g / (1 + np.exp(-g)) * u
            want[0, t] += wi * (silu @ np.asarray(blk0["moe_down"])[e])
        # shared expert
        g = xv @ np.asarray(blk0["w_gate"])
        u = xv @ np.asarray(blk0["w_up"])
        want[0, t] += (g / (1 + np.exp(-g)) * u) @ np.asarray(blk0["w_down"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_moe_expert_parallel_matches_single_device(moe_setup):
    cfg, params = moe_setup
    mesh = make_mesh(dp=1, sp=1, ep=4, tp=2)
    tokens = jax.random.randint(jax.random.key(3), (2, 8), 0, cfg.vocab_size)
    ref = forward_train(params, cfg, tokens)
    p_sh = shard_pytree(params, param_specs(cfg), mesh)
    got = jax.jit(lambda p, t: forward_train(p, cfg, t))(p_sh, tokens)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_moe_train_step_reduces_loss(moe_setup):
    cfg, params = moe_setup
    mesh = make_mesh(dp=1, sp=2, ep=2, tp=2)
    tokens = jax.random.randint(jax.random.key(4), (2, 16), 0, cfg.vocab_size)
    from rbg_tpu.models.training import next_token_loss
    loss0 = float(next_token_loss(params, cfg, tokens))
    _, loss = train_n_steps(cfg, mesh, params, tokens, n=4)
    assert float(loss) < loss0


@pytest.mark.slow
def test_moe_serving_engine(moe_setup):
    """The engine serves MoE models unchanged (paged path uses the same
    block math)."""
    cfg, params = moe_setup
    from rbg_tpu.engine import Engine, EngineConfig, SamplingParams
    from rbg_tpu.models.llama import prefill_and_decode_greedy

    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    expect = [int(t) for t in np.asarray(prefill_and_decode_greedy(
        params, cfg, jnp.asarray([prompt], jnp.int32), 6))[0]]
    eng = Engine(EngineConfig(model="tiny-moe", page_size=8, num_pages=64,
                              max_seq_len=128, prefill_chunk=16,
                              use_pallas="never"), params=params)
    got = eng.generate([prompt], SamplingParams(max_new_tokens=6))[0]
    assert got == expect


# ---- a step of few rows visits hit experts only (llama._moe_mlp_hit) ----


def _toy(E, K, shared):
    import dataclasses
    return dataclasses.replace(
        get_config("tiny-moe"), name=f"toy-e{E}k{K}", hidden_size=64,
        num_heads=2, num_kv_heads=1, num_experts=E, experts_per_token=K,
        moe_intermediate_size=32, moe_shared_expert=shared,
        intermediate_size=48)


def _routed(cfg, params, picks, seed=0):
    """A layer-1 block whose router sends row r to experts ``picks[r]``
    (a spike on each picked expert's logit), and the rows' activations."""
    E, D = cfg.num_experts, cfg.hidden_size
    blk = jax.tree_util.tree_map(lambda a: a[1], params["blocks"])
    x = 0.1 * np.asarray(jax.random.normal(
        jax.random.key(seed), (len(picks), D), jnp.float32))
    if picks[0] is not None:
        blk = dict(blk, router=50.0 * jnp.eye(D, E, dtype=jnp.float32))
        x[:, :E] = 0.0
        for r, experts in enumerate(picks):
            for j, e in enumerate(experts):
                x[r, e] = 1.0 - 0.1 * j
    return blk, jnp.asarray(x)[:, None, :]                    # [rows, 1, D]


HIT_CASES = {
    # name: (E, K, shared expert, picks per row (None: as the weights fall),
    #        live rows (None: all), experts visited (None: whatever is hit))
    "e8k2-rows1": (8, 2, False, [None], None, 2),
    "e8k2-rows2": (8, 2, False, [None] * 2, None, None),
    "e8k2-rows8": (8, 2, False, [None] * 8, None, None),
    "e8k2-every-expert-hit": (
        8, 2, False, [(2 * r % 8, (2 * r + 1) % 8) for r in range(8)],
        None, 8),
    "e8k2-all-rows-agree": (8, 2, False, [(5, 3)] * 8, None, 2),
    "e8k1-one-expert-hit": (8, 1, False, [(6,)] * 8, None, 1),
    "e64k6-shared-rows1": (64, 6, True, [None], None, 6),
    "e64k6-shared-rows2": (64, 6, True, [None] * 2, None, None),
    "e64k6-shared-rows8": (64, 6, True, [None] * 8, None, None),
    # The dead rows route to experts 6 and 7, which no live row wants.
    "e8k2-dead-rows-make-no-expert-live": (
        8, 2, False, [(0, 1), (1, 2), (0, 2), (2, 1)] + [(6, 7)] * 4,
        [True] * 4 + [False] * 4, 3),
    "e8k2-no-row-live": (8, 2, False, [(0, 1)] * 2, [False] * 2, 0),
}


@pytest.mark.parametrize("case", sorted(HIT_CASES))
def test_hit_experts_form_equals_the_dense_dispatch(case):
    from rbg_tpu.models.llama import (_EXPERT_STACKS, _moe_mlp,
                                      _moe_mlp_hit, _route, hit_experts_pay)
    E, K, shared, picks, live, want_visited = HIT_CASES[case]
    cfg = _toy(E, K, shared)
    assert hit_experts_pay(cfg, len(picks))
    params = init_params(cfg, jax.random.key(5))
    blk, xm = _routed(cfg, params, picks)
    live = jnp.asarray([True] * len(picks) if live is None else live)
    stacks = {k: params["blocks"][k] for k in _EXPERT_STACKS}

    dense = np.asarray(_moe_mlp(cfg, blk, xm))
    got, visited = jax.jit(
        lambda b, x, s, m: _moe_mlp_hit(cfg, b, x, s, jnp.int32(1), m[:, None])
    )(blk, xm, stacks, live)

    rows = np.asarray(live)
    if rows.any():
        # Against the output's own size: an expert left out is a tenth of it.
        size = np.abs(dense[rows]).max()
        assert size > 0
        np.testing.assert_allclose(np.asarray(got)[rows], dense[rows],
                                   rtol=1e-4, atol=1e-5 * size)
    hit = (np.asarray(_route(cfg, blk, xm))[rows] > 0).any(axis=(0, 1))
    assert int(visited) == hit.sum()
    if want_visited is not None:
        assert int(visited) == want_visited
    # A dead row gets the shared expert and nothing else.
    if not rows.all() and not shared:
        assert not np.asarray(got)[~rows].any()


def _paged_step(cfg, params, rows, **kw):
    from rbg_tpu.models.llama import forward_paged
    L, KV, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_
    pool = jnp.zeros((L, 2 * rows, 8, KV, hd), cfg.jax_dtype)
    tokens = (jnp.arange(rows, dtype=jnp.int32) * 7 + 3)[:, None]
    return lambda p: forward_paged(
        p, cfg, tokens=tokens, positions=jnp.zeros((rows, 1), jnp.int32),
        token_mask=jnp.ones((rows, 1), bool),
        kv_lens=jnp.ones((rows,), jnp.int32),
        page_table=jnp.arange(2 * rows, dtype=jnp.int32).reshape(rows, 2),
        k_pages=pool, v_pages=pool, use_pallas="never", **kw)


def test_paged_step_of_few_rows_visits_hit_experts_only(moe_setup):
    cfg, params = moe_setup                     # E 4, K 2: up to 4 rows
    dense = jax.jit(_paged_step(cfg, params, 3))(params)
    hit = jax.jit(_paged_step(cfg, params, 3, experts_whole=True))(params)
    assert len(dense) == 5 and len(hit) == 6
    np.testing.assert_allclose(np.asarray(hit[0]), np.asarray(dense[0]),
                               rtol=1e-5, atol=1e-5)
    assert cfg.num_layers * cfg.experts_per_token <= int(hit[5]) \
        <= cfg.num_layers * cfg.num_experts


def test_paged_step_above_the_threshold_is_the_dense_program(moe_setup):
    """Rows x K > 2 x E: nothing to skip, and the program is the one a
    caller gets who never said its experts are whole."""
    cfg, params = moe_setup
    from rbg_tpu.models.llama import hit_experts_pay
    assert hit_experts_pay(cfg, 4) and not hit_experts_pay(cfg, 5)

    def program(**kw):
        def step(p):
            out = _paged_step(cfg, params, 5, **kw)(p)
            assert len(out) == 5 or out[5] is None
            return out[:5]
        return jax.jit(step).lower(params).as_text()

    texts = [program(), program(experts_whole=True)]
    assert texts[0] == texts[1]
    assert "while" in texts[0]


def test_hit_experts_form_under_a_tp_mesh(moe_setup):
    """tp splits every expert's F, not the experts: each device visits the
    same hit experts and reads its own columns of them."""
    cfg, params = moe_setup
    mesh = make_mesh(dp=1, sp=1, ep=1, tp=2)
    want = jax.jit(_paged_step(cfg, params, 2, experts_whole=True))(params)
    p_sh = shard_pytree(params, param_specs(cfg), mesh)
    got = jax.jit(_paged_step(cfg, params, 2, experts_whole=True))(p_sh)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-4, atol=1e-4)
    assert int(got[5]) == int(want[5])


# ---- the visits as one kernel walk (ops/pallas/moe_visit_kernel.py) ----------


def _lane_toy(E, K, shared, held=None, dtype="float32"):
    """A toy whose expert is two lane tiles wide (D 128, F 256), so that a
    walk in tiles of 128 has two steps a visit."""
    import dataclasses
    return dataclasses.replace(
        _toy(E, K, shared), name=f"lane-e{E}k{K}", hidden_size=128,
        num_heads=2, moe_intermediate_size=256, experts_held=held,
        dtype=dtype)


VISIT_CASES = {
    # name: (E, K, shared expert, experts held, dtype, the kernel's tile of F
    #        (None: ``tile_f``'s, the whole 256), picks per row (None: as the
    #        weights fall), live rows (None: all), experts visited)
    "f32-one-tile-rows3": (8, 2, False, None, "float32", None,
                           [None] * 3, None, None),
    "f32-two-tiles-rows3": (8, 2, False, None, "float32", 128,
                            [None] * 3, None, None),
    "bf16-one-tile-rows5": (8, 2, False, None, "bfloat16", None,
                            [None] * 5, None, None),
    "bf16-two-tiles-rows16": (16, 2, False, None, "bfloat16", 128,
                              [None] * 16, None, None),
    "bf16-two-tiles-a-dead-row": (
        8, 2, False, None, "bfloat16", 128,
        [(0, 1), (1, 2), (6, 7)], [True, True, False], 3),
    "f32-no-row-live": (8, 2, False, None, "float32", None,
                        [(0, 1)] * 2, [False] * 2, 0),
    "bf16-two-tiles-no-row-live": (8, 2, False, None, "bfloat16", 128,
                                   [(0, 1)] * 2, [False] * 2, 0),
    "f32-every-expert-hit": (
        8, 2, False, None, "float32", 128,
        [(2 * r % 8, (2 * r + 1) % 8) for r in range(8)], None, 8),
    "f32-one-expert-hit": (8, 1, False, None, "float32", None,
                           [(6,)] * 4, None, 1),
    # The router picks among the published 16; the device holds 4..11.
    "f32-held-a-sub-range": (16, 4, False, (4, 12), "float32", 128,
                             [(3, 4, 7, 12), (5, 7, 11, 15)], None, 4),
    "bf16-held-a-sub-range-shared": (16, 4, True, (4, 12), "bfloat16", None,
                                     [None] * 6, None, None),
    "f32-shared-beside-it": (16, 4, True, None, "float32", 128,
                             [None] * 4, None, None),
}


@pytest.mark.parametrize("case", sorted(VISIT_CASES))
def test_the_visit_kernel_equals_the_xla_loop(case, monkeypatch):
    """``_moe_mlp_hit`` by the kernel (``use_pallas="always"``, interpreted)
    against the XLA loop: the kernel is handed the loop's own arguments, its
    float32 sums equal the loop's within a sum's reordering, the layer's
    output within a rounding of the activations' dtype, the count of
    experts visited to the expert; a call without a live row gives zeros."""
    from rbg_tpu.models import llama
    from rbg_tpu.ops.pallas import moe_visit_kernel as K
    E, Kt, shared, held, dtype, tile, picks, live, want_visited = \
        VISIT_CASES[case]
    cfg = _lane_toy(E, Kt, shared, held, dtype)
    assert llama.hit_experts_pay(cfg, len(picks))
    params = init_params(cfg, jax.random.key(5))
    blk, xm = _routed(cfg, params, picks)
    xm = xm.astype(cfg.jax_dtype)
    live = jnp.asarray([True] * len(picks) if live is None else live)[:, None]
    stacks = {k: params["blocks"][k] for k in llama._EXPERT_STACKS}
    assert stacks["moe_gate"].shape[1] == cfg.experts_here

    real, calls = K.moe_visit_pallas, []

    def spy(*args):
        calls.append((args, real(*args, interpret=True, tile=tile)))
        return calls[-1][1]

    monkeypatch.setattr(K, "moe_visit_pallas", spy)
    want, seen = llama._moe_mlp_hit(cfg, blk, xm, stacks, jnp.int32(1), live,
                                    "never")
    assert not calls
    got, visited = llama._moe_mlp_hit(cfg, blk, xm, stacks, jnp.int32(1),
                                      live, "always")
    (args, summed), = calls
    assert summed.dtype == jnp.float32 and int(visited) == int(seen)
    if want_visited is not None:
        assert int(visited) == want_visited
    loop = np.asarray(llama._visit_loop(*args))
    size = np.abs(loop).max()
    assert (size > 0) == (int(visited) > 0)
    np.testing.assert_allclose(np.asarray(summed), loop, rtol=1e-5,
                               atol=1e-5 * size)
    ulp = 2.0 ** -7 if dtype == "bfloat16" else 1e-5
    out = np.asarray(want, np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), out,
                               rtol=ulp, atol=ulp * np.abs(out).max())
    if not shared:
        assert not np.asarray(got, np.float32)[~np.asarray(live)[:, 0]].any()


def test_served_tokens_are_equal_by_the_visit_kernel_and_by_the_loop(
        moe_setup, monkeypatch, interpreted):
    """A served ``tiny-moe``, greedy: the same tokens with the decode
    steps' visits by the kernel (``use_pallas="always"``, every kernel the
    model reaches interpreted) and by the XLA loop, and the same count of
    experts visited."""
    from rbg_tpu.engine import Engine, EngineConfig, SamplingParams
    from rbg_tpu.ops.pallas import moe_visit_kernel as K
    cfg, params = moe_setup
    real, walked = K.moe_visit_pallas, []
    monkeypatch.setattr(K, "moe_visit_pallas",
                        lambda *args: walked.append(1) or real(*args))
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8]]
    served = {}
    for policy in ("never", "always"):
        eng = Engine(EngineConfig(model="tiny-moe", page_size=8, num_pages=64,
                                  max_seq_len=128, prefill_chunk=16,
                                  use_pallas=policy), params=params)
        assert not walked
        served[policy] = (
            eng.generate(prompts, SamplingParams(max_new_tokens=8)),
            eng.metrics["moe_experts_visited"])
        assert served[policy][1] > 0
    assert walked
    assert served["always"] == served["never"]


def test_the_kernel_calls_are_named_as_the_catalog_says():
    """``obs/names.py::KERNEL_CALLS``: each is the jitted wrapper of a
    Pallas kernel in ``ops/pallas`` under that very name, which is what a
    device trace prints and the benchmark's ``kernel.*`` metrics read."""
    from rbg_tpu.obs.names import KERNEL_CALLS
    from rbg_tpu.ops import pallas
    modules = {pallas.home(kernel) for kernel in pallas.KERNELS}
    assert "_moe_visit_call" in KERNEL_CALLS
    for name in KERNEL_CALLS:
        owners = [m for m in modules if name in vars(m)]
        assert owners, name
        assert getattr(owners[0], name).__name__ == name
