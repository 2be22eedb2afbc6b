"""Mixture-of-Experts layer with sort-based (gather) dispatch.

DESIGN.md §4: top-k expert gating is the architectural analogue of
Cerebra-H's event-gated weight-row fetch — only routed experts' weights
participate, so compiled FLOPs track *active* parameters. We therefore use
capacity-bounded gather dispatch (GShard-style, like MaxText) rather than
dense one-hot einsum: HLO FLOPs stay ~= top_k/n_experts of the dense cost,
which is what makes the MoE rooflines in EXPERIMENTS.md meaningful.

Baseline sharding runs experts tensor-parallel (ffn dim over ``model``);
the expert-parallel (experts over ``model`` + token all-to-all) variant is
explored in the §Perf hillclimb.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.common import MoEConfig, TransformerConfig, dense_init

__all__ = ["init_moe", "moe_forward", "moe_forward_ep"]


def init_moe(key, cfg: TransformerConfig) -> dict:
    m = cfg.moe
    d, f, E = cfg.d_model, cfg.d_ff, m.n_experts
    k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 7)
    p = {
        "router": dense_init(k1, (d, E)),
        "w_gate": dense_init(k2, (E, d, f), in_axis=1),
        "w_up": dense_init(k3, (E, d, f), in_axis=1),
        "w_down": dense_init(k4, (E, f, d), in_axis=1),
    }
    if m.shared_expert:
        p["shared_w_gate"] = dense_init(k5, (d, f))
        p["shared_w_up"] = dense_init(k6, (d, f))
        p["shared_w_down"] = dense_init(k7, (f, d))
    return p


def moe_forward(p: dict, x, cfg: TransformerConfig, *,
                dropless: bool = False):
    """x: (B, S, d) -> (out (B,S,d), aux_loss scalar).

    ``dropless=True`` sizes expert buffers to hold EVERY routed token
    (C = T): bit-exact routing with static shapes. Used by the decode path,
    where T = batch is small and per-token exactness matters (capacity
    drops during decode are nondeterministic quality loss). Training and
    prefill keep GShard capacity semantics (C = T*k/E * capacity_factor).
    """
    m: MoEConfig = cfg.moe
    B, S, d = x.shape
    E, k = m.n_experts, m.top_k
    T = B * S
    xf = x.reshape(T, d)

    logits = (xf.astype(jnp.float32)
              @ p["router"].astype(jnp.float32))        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)        # (T, k)
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    # load-balance aux loss (Switch): E * sum_e f_e * P_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[gate_idx.reshape(-1)].add(
        jnp.ones((T * k,), jnp.float32)) / (T * k)
    aux = m.router_aux_weight * E * jnp.sum(me * ce)

    # ---- sort-based capacity dispatch -------------------------------------
    # (hypothesis A6 — GShard one-hot einsum dispatch — REFUTED for this
    # regime: the dense (T,E,C) dispatch/combine einsums cost T*E*C*d
    # flops, 10-30x the expert compute at top-1/top-2 capacities. The
    # sort+scatter form keeps HLO flops proportional to ACTIVE experts —
    # the Cerebra-H event-gating analogue; see §Perf log.)
    C = T if dropless else int(np.ceil(T * k / E * m.capacity_factor))
    C = max(8, -(-C // 8) * 8)  # pad to VPU sublane multiple
    expert_flat = gate_idx.reshape(-1)                   # (T*k,)
    token_flat = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    weight_flat = gate_vals.reshape(-1)

    order = jnp.argsort(expert_flat)                     # stable in jnp
    se = expert_flat[order]
    st = token_flat[order]
    sw = weight_flat[order]
    counts = jnp.zeros((E,), jnp.int32).at[se].add(1)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(T * k, dtype=jnp.int32) - starts[se]
    keep = pos_in_e < C                                  # capacity drop
    slot = jnp.where(keep, se * C + pos_in_e, E * C)     # overflow -> trash

    # gather tokens into expert buffers (E*C+1 rows; last row = trash)
    buf = jnp.zeros((E * C + 1, d), x.dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], xf[st], 0.0))
    eb = buf[: E * C].reshape(E, C, d)

    # ---- expert computation (batched einsum over experts) ----
    if cfg.mlp_kind in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp_kind == "swiglu" else jax.nn.gelu
        h = act(jnp.einsum("ecd,edf->ecf", eb, p["w_gate"].astype(x.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", eb, p["w_up"].astype(x.dtype))
    else:
        h = jax.nn.gelu(
            jnp.einsum("ecd,edf->ecf", eb, p["w_up"].astype(x.dtype)))
    y = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(x.dtype))
    y = y.reshape(E * C, d)

    # ---- combine back to token order ----
    contrib = jnp.where(keep[:, None],
                        sw[:, None].astype(x.dtype)
                        * y[jnp.clip(slot, 0, E * C - 1)], 0.0)
    out = jnp.zeros((T, d), x.dtype).at[st].add(contrib)

    if m.shared_expert:
        act = jax.nn.silu
        hs = act(xf @ p["shared_w_gate"].astype(x.dtype)) * (
            xf @ p["shared_w_up"].astype(x.dtype))
        out = out + hs @ p["shared_w_down"].astype(x.dtype)

    return out.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Explicit expert parallelism via shard_map (DESIGN.md §7 / §Perf cell A).
#
# GSPMD makes pathological choices for gather-based MoE dispatch under every
# sharding we measured (EXPERIMENTS.md cell A: six refuted hypotheses). This
# path takes the collectives out of GSPMD's hands: experts live on data-axis
# rows (E % n_data == 0), tokens move by ONE all_to_all each way, expert
# matmuls stay tensor-parallel over `model` with a single psum. Enabled with
# TransformerConfig.moe_ep=true (llama4: 16 experts on the 16-way data axis).
# ---------------------------------------------------------------------------

def _ep_eligible(cfg, mesh) -> bool:
    return (mesh is not None and not mesh.empty
            and "data" in mesh.axis_names
            and cfg.moe.n_experts % mesh.shape["data"] == 0
            and cfg.d_ff % max(mesh.shape.get("model", 1), 1) == 0)


def moe_forward_ep(p: dict, x, cfg: TransformerConfig, *,
                   dropless: bool = False):
    """Expert-parallel MoE block. x: (B, S, d) batch-sharded over data."""
    from jax.interpreters import pxla
    mesh = pxla.thread_resources.env.physical_mesh
    if not _ep_eligible(cfg, mesh):
        return moe_forward(p, x, cfg, dropless=dropless)
    m: MoEConfig = cfg.moe
    E, k = m.n_experts, m.top_k
    n_ed = mesh.shape["data"]
    epr = E // n_ed                       # experts per data row
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    w_spec = jax.tree.map(lambda _: P(), p)
    for key in ("w_gate", "w_up"):
        w_spec[key] = P("data", None, "model")
    w_spec["w_down"] = P("data", "model", None)
    for key in ("shared_w_gate", "shared_w_up"):
        if key in p:
            w_spec[key] = P(None, "model")
    if "shared_w_down" in p:
        w_spec["shared_w_down"] = P("model", None)

    def block(xl, pl):
        # xl: (B_local, S, d) on this (data-row, model-col) device
        Bl, S, d = xl.shape
        T = Bl * S
        xf = xl.reshape(T, d)
        logits = (xf.astype(jnp.float32)
                  @ pl["router"].astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

        me = jnp.mean(probs, axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[gate_idx.reshape(-1)].add(
            jnp.ones((T * k,), jnp.float32)) / (T * k)
        aux = m.router_aux_weight * E * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, "data")

        # per-expert capacity of the LOCAL contribution (C = T is exactly
        # dropless per source shard: one expert can take every local token)
        C = T if dropless else int(np.ceil(T * k / E * m.capacity_factor))
        C = max(8, -(-C // 8) * 8)
        expert_flat = gate_idx.reshape(-1)
        token_flat = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        weight_flat = gate_vals.reshape(-1)
        order = jnp.argsort(expert_flat)
        se, st, sw = expert_flat[order], token_flat[order], weight_flat[order]
        counts = jnp.zeros((E,), jnp.int32).at[se].add(1)
        starts = jnp.cumsum(counts) - counts
        pos_in_e = jnp.arange(T * k, dtype=jnp.int32) - starts[se]
        keep = pos_in_e < C
        slot = jnp.where(keep, se * C + pos_in_e, E * C)

        send = jnp.zeros((E * C + 1, d), xl.dtype)
        send = send.at[slot].set(jnp.where(keep[:, None], xf[st], 0.0))
        send = send[: E * C].reshape(n_ed, epr * C, d)
        # one all-to-all out: row j receives every shard's tokens for its
        # experts -> (n_ed src rows, epr*C, d)
        recv = jax.lax.all_to_all(send, "data", split_axis=0, concat_axis=0,
                                  tiled=False)
        eb = (recv.reshape(n_ed, epr, C, d)
              .transpose(1, 0, 2, 3).reshape(epr, n_ed * C, d))

        # local experts, ffn TP over `model` (single psum on the way out)
        wg, wu, wd = pl["w_gate"], pl["w_up"], pl["w_down"]
        act = jax.nn.silu if cfg.mlp_kind == "swiglu" else jax.nn.gelu
        h = act(jnp.einsum("ecd,edf->ecf", eb, wg.astype(xl.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", eb, wu.astype(xl.dtype))
        y = jnp.einsum("ecf,efd->ecd", h, wd.astype(xl.dtype))
        y = jax.lax.psum(y, "model")

        # route back: invert the transpose, all-to-all home
        y = (y.reshape(epr, n_ed, C, d).transpose(1, 0, 2, 3)
             .reshape(n_ed, epr * C, d))
        back = jax.lax.all_to_all(y, "data", split_axis=0, concat_axis=0,
                                  tiled=False)
        back = back.reshape(E * C, d)
        contrib = jnp.where(keep[:, None],
                            sw[:, None].astype(xl.dtype)
                            * back[jnp.clip(slot, 0, E * C - 1)], 0.0)
        out = jnp.zeros((T, d), xl.dtype).at[st].add(contrib)

        if m.shared_expert:
            hs = jax.nn.silu(xf @ pl["shared_w_gate"].astype(xl.dtype)) * (
                xf @ pl["shared_w_up"].astype(xl.dtype))
            out = out + jax.lax.psum(
                hs @ pl["shared_w_down"].astype(xl.dtype), "model")
        return out.reshape(Bl, S, d), aux

    fn = jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(batch_axes if len(batch_axes) > 1 else batch_axes[0]),
                  w_spec),
        out_specs=(P(batch_axes if len(batch_axes) > 1 else batch_axes[0]),
                   P()),
        check_vma=False)
    return fn(x, p)
