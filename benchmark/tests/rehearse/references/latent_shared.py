"""The rehearsal's reference: a block the default reference does not compute.

Multi-head latent attention as published for DeepSeek-V2 (the keys and
values of every head are up-projections of one cached latent, beside one
rotary key that all heads share), written in the plain, materialised form,
and a sparse block whose routed experts have a width of their own beside a
shared expert that every token passes through. Sizes that are no published
key come from the configuration file's ``preset``, which is also what the
program is built from. Nothing of ``rbg_tpu.models`` or ``rbg_tpu.ops``;
the general pieces are the default module's (both are the benchmark's).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from harness import reference as base

CONTROLS = base.CONTROLS


def sizes(cfg: dict) -> dict:
    z, p = base.sizes(cfg), cfg["preset"]
    z.update(dc=p["kv_lora_rank"], dn=p["qk_nope_head_dim"],
             dr=p["qk_rope_head_dim"], dv=p["v_head_dim"],
             f_routed=p["moe_intermediate_size"],
             f_shared=p["moe_shared_expert_size"])
    return z


def make_params(cfg: dict, seed: int):
    z = sizes(cfg)
    d, h, L, E = z["d"], z["h"], z["L"], z["E"]
    s_in, s_out = base.S_IN, base.S_IN / math.sqrt(2.0 * L)
    blocks = {
        "wq": ((L, d, h * (z["dn"] + z["dr"])), s_in),
        "w_dkv": ((L, d, z["dc"] + z["dr"]), s_in),
        "w_uk": ((L, z["dc"], h * z["dn"]), s_in),
        "w_uv": ((L, z["dc"], h * z["dv"]), s_in),
        "wo": ((L, h * z["dv"], d), s_out),
        "w_gate": ((L, d, z["f_shared"]), s_in),
        "w_up": ((L, d, z["f_shared"]), s_in),
        "w_down": ((L, z["f_shared"], d), s_out),
        "router": ((L, d, E), s_in),
        "moe_gate": ((L, E, d, z["f_routed"]), s_in),
        "moe_up": ((L, E, d, z["f_routed"]), s_in),
        "moe_down": ((L, E, z["f_routed"], d), s_out),
    }
    random = {("embed",): ((z["v"], d), s_in),
              ("lm_head",): ((d, z["v"]), s_in)}
    random.update({("blocks", n): v for n, v in blocks.items()})
    ones = {("blocks", "attn_norm"): (L, d), ("blocks", "mlp_norm"): (L, d),
            ("blocks", "kv_norm"): (L, z["dc"]), ("final_norm",): (d,)}
    return base.random_params(random, ones, jnp.dtype(cfg["torch_dtype"]),
                              seed)


def _latent_attention(z, blk, x, quant):
    T, h = x.shape[0], z["h"]
    dc, dn, dr, dv = z["dc"], z["dn"], z["dr"], z["dv"]
    pos = jnp.arange(T)
    q = base._mm(x, blk["wq"], quant).reshape(T, h, dn + dr)
    q_pe = base._rope(q[..., dn:], pos, z["theta"])
    kv = base._mm(x, blk["w_dkv"], quant)
    c = base._rms_norm(kv[:, :dc], blk["kv_norm"], z["eps"])     # the latent
    k_pe = base._rope(kv[:, None, dc:], pos, z["theta"])[:, 0]   # one key
    if quant is not None:       # the control's cache holds them rounded
        kv_quant = quant.removeprefix("kv_")
        c, k_pe = (base._fake_quant(c, kv_quant),
                   base._fake_quant(k_pe, kv_quant))
    k_nope = base._mm(c, blk["w_uk"], quant).reshape(T, h, dn)
    v = base._mm(c, blk["w_uv"], quant).reshape(T, h, dv)
    s = (jnp.einsum("thn,shn->hts", q[..., :dn], k_nope)
         + jnp.einsum("thr,sr->hts", q_pe, k_pe)) / math.sqrt(dn + dr)
    s = jnp.where((pos[:, None] >= pos[None, :])[None], s, -jnp.inf)
    o = jnp.einsum("hts,shv->thv", jax.nn.softmax(s, axis=-1), v)
    return base._mm(o.reshape(T, h * dv), blk["wo"], quant)


@functools.partial(jax.jit, static_argnames=("zt", "start", "quant"))
def _forward(params, tokens, zt, start, quant):
    z = dict(zt)
    x = params["embed"].astype(jnp.float32)[tokens]
    if quant is not None and not quant.startswith("kv_"):
        x = base._fake_quant(x, quant)

    def layer(x, blk):
        a = base._rms_norm(x, blk["attn_norm"], z["eps"])
        x = x + _latent_attention(z, blk, a, quant)
        m = base._rms_norm(x, blk["mlp_norm"], z["eps"])
        y = base._moe(z, blk, m, quant) + base._swiglu(
            m, blk["w_gate"], blk["w_up"], blk["w_down"], quant)
        return x + y, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = base._rms_norm(x[start:], params["final_norm"], z["eps"])
    return jax.nn.log_softmax(base._mm(x, params["lm_head"], quant), axis=-1)


def chosen_logprobs(cfg: dict, params, prompt, served, quant=None):
    seq = list(prompt) + list(served)
    with jax.default_matmul_precision("highest"):
        lp = _forward(params, jnp.asarray(seq, jnp.int32),
                      tuple(sorted(sizes(cfg).items())), len(prompt) - 1,
                      quant)
    return lp[jnp.arange(len(served)), jnp.asarray(served, jnp.int32)]
