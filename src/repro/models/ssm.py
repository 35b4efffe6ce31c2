"""Linear-recurrence sequence mixers: mLSTM (xLSTM) and Mamba2 (SSD).

Both are instances of one scalar-decay gated linear recurrence per head:

    S_t = f_t * S_{t-1} + i_t * k_t v_t^T          (state:  dk x dv)
    n_t = f_t * n_{t-1} + i_t * k_t                (normalizer, mLSTM only)
    y_t = q_t @ S_t [/ max(|q_t . n_t|, 1)]

computed in **chunked** form (the TPU-native schedule — DESIGN.md §3): an
intra-chunk attention-like term plus an inter-chunk contribution through the
carried state. Decays are handled in log space; since f_t <= 1 every
``exp(logB_j - logB_u)`` with u <= j is <= 1, so the chunked form is stable
without a separate stabilizer state.

Deviations from the papers (recorded in DESIGN.md §7):
  * mLSTM uses the sigmoid input/forget gates of xLSTM-7B ("mLSTMsig") rather
    than the exp-gate + stabilizer of the v1 paper — same state equation,
    simpler chunking, and the published 7B shows parity.
  * Mamba2 keeps the depthwise conv + gating + D-skip structure but drops
    grouped B/C (single group) — zamba2's config uses one group.

Decode steps update ``(S, n)`` in O(1) per token — this is what makes the
``long_500k`` cells tractable for the ssm/hybrid architectures.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers

Array = jax.Array
Params = Dict[str, Array]


class RecurrentState(NamedTuple):
    s: Array   # (B, H, dk, dv)
    n: Array   # (B, H, dk)


def glr_chunked(
    q: Array,        # (B, S, H, dk)
    k: Array,        # (B, S, H, dk)
    v: Array,        # (B, S, H, dv)
    log_f: Array,    # (B, S, H)  log forget gate, <= 0
    gate_i: Array,   # (B, S, H)  input gate / step scale, >= 0
    state: Optional[RecurrentState] = None,
    *,
    chunk: int = 256,
    normalize: bool = False,
    return_raw: bool = False,
) -> Tuple[Array, RecurrentState]:
    """Chunked gated linear recurrence. Returns (y (B,S,H,dv), final state).

    ``return_raw=True`` returns ``((y_unnormalized, n_dot), state)`` so a
    caller can add cross-device contributions before normalizing (the
    sequence-parallel path)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    c = min(chunk, s)
    pad = (-s) % c
    if pad:
        zf = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        q, k, v, gate_i = map(zf, (q, k, v, gate_i))
        log_f = jnp.pad(log_f, ((0, 0), (0, pad), (0, 0)))  # pad f=1 -> logf=0
    nc = (s + pad) // c

    def resh(a):
        return a.reshape(b, nc, c, *a.shape[2:]).swapaxes(0, 1)

    qb, kb, vb, fb, ib = map(resh, (q, k, v, log_f, gate_i))

    if state is None:
        state = RecurrentState(
            s=jnp.zeros((b, h, dk, dv), jnp.float32),
            n=jnp.zeros((b, h, dk), jnp.float32),
        )

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def step(carry: RecurrentState, inp):
        qc, kc, vc, lfc, ic = inp           # (B,c,H,*)
        lb = jnp.cumsum(lfc.astype(jnp.float32), axis=1)       # (B,c,H)
        total = lb[:, -1]                                      # (B,H)
        qf = qc.astype(jnp.float32) * jnp.exp(lb)[..., None]
        # inter-chunk: decayed query against carried state
        inter = jnp.einsum("bchk,bhkv->bchv", qf, carry.s)
        inter_n = jnp.einsum("bchk,bhk->bch", qf, carry.n)
        # intra-chunk: masked decay-weighted attention
        ratio = lb[:, :, None, :] - lb[:, None, :, :]          # (B,c_q,c_u,H)
        mask = jnp.tril(jnp.ones((c, c), bool))
        w = jnp.where(mask[None, :, :, None], jnp.exp(ratio), 0.0)
        a = jnp.einsum("bchk,buhk->bcuh", qc.astype(jnp.float32),
                       kc.astype(jnp.float32))
        a = a * w * ic[:, None, :, :].astype(jnp.float32)      # (B,c_q,c_u,H)
        intra = jnp.einsum("bcuh,buhv->bchv", a, vc.astype(jnp.float32))
        intra_n = jnp.sum(a, axis=2)                           # (B,c_q,H)
        y = inter + intra
        n_dot = inter_n + intra_n
        if normalize and not return_raw:
            y = y / jnp.maximum(jnp.abs(n_dot), 1.0)[..., None]
        # state update
        kf = kc.astype(jnp.float32) * (
            jnp.exp(total[:, None, :] - lb) * ic.astype(jnp.float32)
        )[..., None]
        s_new = jnp.exp(total)[..., None, None] * carry.s + jnp.einsum(
            "buhk,buhv->bhkv", kf, vc.astype(jnp.float32)
        )
        n_new = jnp.exp(total)[..., None] * carry.n + jnp.sum(kf, axis=1)
        out = (y, n_dot) if return_raw else y
        return RecurrentState(s_new, n_new), out

    final, yb = jax.lax.scan(step, state, (qb, kb, vb, fb, ib))
    if return_raw:
        ys, ns = yb
        y = ys.swapaxes(0, 1).reshape(b, nc * c, h, dv)[:, :s]
        ndot = ns.swapaxes(0, 1).reshape(b, nc * c, h)[:, :s]
        return (y, ndot), final
    y = yb.swapaxes(0, 1).reshape(b, nc * c, h, dv)[:, :s]
    return y.astype(v.dtype), final


def glr_shardmapped(
    q: Array, k: Array, v: Array, log_f: Array, gate_i: Array,
    *,
    seq_axis: str,
    chunk: int = 256,
    normalize: bool = False,
    return_state: bool = False,
):
    """shard_map wrapper: sequence-parallel GLR over the ambient mesh."""
    from jax.sharding import PartitionSpec as P

    spec4 = P(None, seq_axis, None, None)
    spec3 = P(None, seq_axis, None)
    rep4 = P(None, None, None, None)
    rep3 = P(None, None, None)
    out_specs = (spec4, RecurrentState(rep4, rep3)) if return_state else spec4
    return jax.shard_map(
        lambda qq, kk, vv, lf, gi: glr_sequence_parallel(
            qq, kk, vv, lf, gi, seq_axis=seq_axis, chunk=chunk,
            normalize=normalize, return_state=return_state,
        ),
        in_specs=(spec4, spec4, spec4, spec3, spec3),
        out_specs=out_specs,
        axis_names={seq_axis},
    )(q, k, v, log_f, gate_i)


def glr_sequence_parallel(
    q: Array, k: Array, v: Array, log_f: Array, gate_i: Array,
    *,
    seq_axis: str,
    chunk: int = 256,
    normalize: bool = False,
    return_state: bool = False,
):
    """Sequence-parallel GLR for inside ``shard_map`` (LASP-style).

    The recurrence over a token span is an affine state map ``S -> a S + B``
    (``a = exp(sum log_f)``, ``B`` = span's accumulated kv outer products),
    and affine maps compose associatively — so devices compute their local
    span with a zero initial state, run a log-round ppermute prefix scan of
    ``(log a, S, n)`` along ``seq_axis``, and add the inter-device
    contribution ``B_t * q_t @ S_prefix`` before normalizing. Communication:
    log2(P) state-sized ppermutes per layer instead of replicating
    activations (EXPERIMENTS.md §Perf, hillclimb B).
    """
    b, _, h, dk = q.shape
    dv = v.shape[-1]
    state0 = RecurrentState(  # pcast: fresh zeros inside shard_map (vma)
        s=jax.lax.pcast(jnp.zeros((b, h, dk, dv), jnp.float32), (seq_axis,),
                        to="varying"),
        n=jax.lax.pcast(jnp.zeros((b, h, dk), jnp.float32), (seq_axis,),
                        to="varying"),
    )
    (y_raw, ndot), st = glr_chunked(
        q, k, v, log_f, gate_i, state0, chunk=chunk, normalize=normalize,
        return_raw=True,
    )
    s = y_raw.shape[1]

    n_dev = jax.lax.axis_size(seq_axis)
    idx = jax.lax.axis_index(seq_axis)
    log_a = jnp.sum(log_f.astype(jnp.float32), axis=1)       # (B, H)

    # inclusive prefix scan (Hillis-Steele) of the affine maps
    inc = (log_a, st.s, st.n)
    shift = 1
    while shift < n_dev:
        perm = [(i, i + shift) for i in range(n_dev - shift)]
        prev = jax.tree.map(
            lambda t: jax.lax.ppermute(t, seq_axis, perm), inc
        )
        use = idx >= shift
        la_p, s_p, n_p = prev
        la, s_c, n_c = inc
        a_c = jnp.exp(la)
        combined = (
            jnp.where(use, la_p + la, la),
            jnp.where(use, a_c[..., None, None] * s_p + s_c, s_c),
            jnp.where(use, a_c[..., None] * n_p + n_c, n_c),
        )
        inc = combined
        shift *= 2
    # exclusive prefix: shift the inclusive scan forward by one device
    perm1 = [(i, i + 1) for i in range(n_dev - 1)]
    exc = jax.tree.map(lambda t: jax.lax.ppermute(t, seq_axis, perm1), inc)
    first = idx == 0
    s_pre = jnp.where(first, jnp.zeros_like(exc[1]), exc[1])
    n_pre = jnp.where(first, jnp.zeros_like(exc[2]), exc[2])

    # inter-device contribution at every local position
    lb = jnp.cumsum(log_f.astype(jnp.float32), axis=1)        # (B, s, H)
    qf = q.astype(jnp.float32) * jnp.exp(lb)[..., None]
    y = y_raw + jnp.einsum("bshk,bhkv->bshv", qf, s_pre)
    if normalize:
        nd = ndot + jnp.einsum("bshk,bhk->bsh", qf, n_pre)
        y = y / jnp.maximum(jnp.abs(nd), 1.0)[..., None]
    y = y.astype(v.dtype)
    if not return_state:
        return y
    # global final state = last device's inclusive scan, broadcast via psum
    last = idx == n_dev - 1
    s_fin = jax.lax.psum(jnp.where(last, inc[1], jnp.zeros_like(inc[1])),
                         seq_axis)
    n_fin = jax.lax.psum(jnp.where(last, inc[2], jnp.zeros_like(inc[2])),
                         seq_axis)
    return y, RecurrentState(s_fin, n_fin)


def glr_decode_step(
    q: Array,        # (B, H, dk)
    k: Array,        # (B, H, dk)
    v: Array,        # (B, H, dv)
    log_f: Array,    # (B, H)
    gate_i: Array,   # (B, H)
    state: RecurrentState,
    *,
    normalize: bool = False,
) -> Tuple[Array, RecurrentState]:
    """O(1) single-token recurrence update."""
    f = jnp.exp(log_f.astype(jnp.float32))[..., None, None]
    kv = jnp.einsum("bhk,bhv->bhkv", k.astype(jnp.float32),
                    v.astype(jnp.float32))
    s_new = f * state.s + gate_i.astype(jnp.float32)[..., None, None] * kv
    n_new = f[..., 0] * state.n + gate_i.astype(jnp.float32)[..., None] * \
        k.astype(jnp.float32)
    y = jnp.einsum("bhk,bhkv->bhv", q.astype(jnp.float32), s_new)
    if normalize:
        nd = jnp.einsum("bhk,bhk->bh", q.astype(jnp.float32), n_new)
        y = y / jnp.maximum(jnp.abs(nd), 1.0)[..., None]
    return y.astype(v.dtype), RecurrentState(s_new, n_new)


# ---------------------------------------------------------------------------
# mLSTM block (xLSTM)
# ---------------------------------------------------------------------------


def init_mlstm(key: Array, d: int, expand: int, heads: int, dtype) -> Params:
    d_inner = d * expand
    dqk = d_inner // 2  # xLSTM qk-dim factor 0.5
    ks = jax.random.split(key, 6)
    s = d ** -0.5
    return {
        "wq": (jax.random.normal(ks[0], (d, dqk)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, dqk)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, d_inner)) * s).astype(dtype),
        "wo_gate": (jax.random.normal(ks[3], (d, d_inner)) * s).astype(dtype),
        "w_if": (jax.random.normal(ks[4], (d, 2 * heads)) * s).astype(dtype),
        # forget bias ~ +3 biases toward long memory (xLSTM init)
        "b_if": jnp.concatenate(
            [jnp.zeros((heads,)), 3.0 * jnp.ones((heads,))]
        ).astype(dtype),
        "out_norm": layers.init_rms_norm(d_inner, dtype),
        "wd": (jax.random.normal(ks[5], (d_inner, d)) * (d_inner ** -0.5)).astype(dtype),
    }


def _mlstm_gates(params: Params, x: Array, heads: int, compute_dtype):
    b, s, d = x.shape
    xc = x.astype(compute_dtype)
    d_inner = params["wv"].shape[1]
    dqk = params["wq"].shape[1]
    q = (xc @ params["wq"].astype(compute_dtype)).reshape(b, s, heads, dqk // heads)
    k = (xc @ params["wk"].astype(compute_dtype)).reshape(b, s, heads, dqk // heads)
    k = k * ((dqk // heads) ** -0.5)
    v = (xc @ params["wv"].astype(compute_dtype)).reshape(b, s, heads, d_inner // heads)
    gif = xc @ params["w_if"].astype(compute_dtype) + params["b_if"].astype(compute_dtype)
    gi, gf = gif[..., :heads], gif[..., heads:]
    log_f = jax.nn.log_sigmoid(gf.astype(jnp.float32))
    gate_i = jax.nn.sigmoid(gi.astype(jnp.float32))
    return q, k, v, log_f, gate_i


def mlstm_block(
    params: Params, x: Array, heads: int, chunk: int, compute_dtype,
    seq_axis: Optional[str] = None,
) -> Array:
    """Sequence-mode mLSTM mixer (pre-norm residual handled by caller).

    ``seq_axis`` switches the recurrence to the sequence-parallel prefix-scan
    form (shard_map over that mesh axis); projections/norms stay under GSPMD
    with sequence-sharded activations.
    """
    b, s, d = x.shape
    q, k, v, log_f, gate_i = _mlstm_gates(params, x, heads, compute_dtype)
    if seq_axis is None:
        y, _ = glr_chunked(q, k, v, log_f, gate_i, chunk=chunk, normalize=True)
    else:
        y = glr_shardmapped(q, k, v, log_f, gate_i, seq_axis=seq_axis,
                            chunk=chunk, normalize=True)
    y = y.reshape(b, s, -1)
    y = layers.rms_norm(y, params["out_norm"])
    o = jax.nn.sigmoid(x.astype(compute_dtype) @ params["wo_gate"].astype(compute_dtype))
    return (o * y) @ params["wd"].astype(compute_dtype)


def mlstm_decode(
    params: Params, x: Array, state: RecurrentState, heads: int, compute_dtype
) -> Tuple[Array, RecurrentState]:
    """x: (B, 1, d) -> (B, 1, d) plus updated recurrent state."""
    b = x.shape[0]
    q, k, v, log_f, gate_i = _mlstm_gates(params, x, heads, compute_dtype)
    y, state = glr_decode_step(
        q[:, 0], k[:, 0], v[:, 0], log_f[:, 0], gate_i[:, 0], state,
        normalize=True,
    )
    y = y.reshape(b, 1, -1)
    y = layers.rms_norm(y, params["out_norm"])
    o = jax.nn.sigmoid(x.astype(compute_dtype) @ params["wo_gate"].astype(compute_dtype))
    return (o * y) @ params["wd"].astype(compute_dtype), state


def mlstm_state_shape(b: int, d: int, expand: int, heads: int):
    d_inner = d * expand
    dk = (d_inner // 2) // heads
    dv = d_inner // heads
    return RecurrentState(
        s=jnp.zeros((b, heads, dk, dv), jnp.float32),
        n=jnp.zeros((b, heads, dk), jnp.float32),
    )


# ---------------------------------------------------------------------------
# Mamba2 block (SSD)
# ---------------------------------------------------------------------------


class MambaState(NamedTuple):
    ssm: RecurrentState      # (B, H, dstate, headdim)
    conv: Array              # (B, conv_w - 1, d_conv_channels)


def init_mamba2(
    key: Array, d: int, expand: int, state_dim: int, heads: int,
    conv_width: int, dtype,
) -> Params:
    d_inner = d * expand
    headdim = d_inner // heads
    assert headdim * heads == d_inner
    ks = jax.random.split(key, 6)
    s = d ** -0.5
    # Input projections are separate params (not one fused matmul) so the
    # tensor-parallel dims shard cleanly: w_x / w_z are column-parallel over
    # d_inner; w_bc / w_dt are tiny and replicated (DESIGN.md §5).
    return {
        "w_x": (jax.random.normal(ks[0], (d, d_inner)) * s).astype(dtype),
        "w_z": (jax.random.normal(ks[1], (d, d_inner)) * s).astype(dtype),
        "w_bc": (jax.random.normal(ks[2], (d, 2 * state_dim)) * s).astype(dtype),
        "w_dt": (jax.random.normal(ks[3], (d, heads)) * s).astype(dtype),
        # depthwise conv applies per-channel: x-channels sharded like w_x's
        # output, bc-channels replicated — kept as two separate filters.
        "conv_x_w": (jax.random.normal(ks[4], (conv_width, d_inner)) * 0.1).astype(dtype),
        "conv_x_b": jnp.zeros((d_inner,), dtype),
        "conv_bc_w": (jax.random.normal(ks[5], (conv_width, 2 * state_dim)) * 0.1).astype(dtype),
        "conv_bc_b": jnp.zeros((2 * state_dim,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, heads)).astype(dtype),  # A = -exp
        "dt_bias": jnp.log(jnp.expm1(jnp.full((heads,), 0.01))).astype(dtype),
        "d_skip": jnp.ones((heads,), dtype),
        "out_norm": layers.init_rms_norm(d_inner, dtype),
        "wd": (jax.random.normal(ks[0], (d_inner, d)) * (d_inner ** -0.5)).astype(dtype),
    }


def _causal_conv(x: Array, w: Array, b: Array, history: Optional[Array] = None):
    """Depthwise causal conv. x (B,S,C), w (W,C). Returns (y, new_history)."""
    width = w.shape[0]
    if history is None:
        history = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    xh = jnp.concatenate([history, x], axis=1)
    y = sum(
        xh[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(width)
    )
    return y + b[None, None, :], xh[:, -(width - 1):, :]


def _mamba_core_inputs(params: Params, x: Array, heads: int, state_dim: int,
                       compute_dtype, conv_history=None):
    b, s, d = x.shape
    d_inner = params["w_x"].shape[1]
    headdim = d_inner // heads
    xc = x.astype(compute_dtype)
    xi = xc @ params["w_x"].astype(compute_dtype)
    z = xc @ params["w_z"].astype(compute_dtype)
    bc = xc @ params["w_bc"].astype(compute_dtype)
    dt_raw = xc @ params["w_dt"].astype(compute_dtype)
    if conv_history is None:
        hist_x, hist_bc = None, None
    else:
        hist_x = conv_history[..., :d_inner]
        hist_bc = conv_history[..., d_inner:]
    conv_x, new_hx = _causal_conv(
        xi, params["conv_x_w"].astype(compute_dtype),
        params["conv_x_b"].astype(compute_dtype), hist_x,
    )
    conv_bc, new_hbc = _causal_conv(
        bc, params["conv_bc_w"].astype(compute_dtype),
        params["conv_bc_b"].astype(compute_dtype), hist_bc,
    )
    new_hist = jnp.concatenate([new_hx, new_hbc], axis=-1)
    xi = jax.nn.silu(conv_x).reshape(b, s, heads, headdim)
    conv_bc = jax.nn.silu(conv_bc)
    bmat = conv_bc[..., :state_dim]
    cmat = conv_bc[..., state_dim:]
    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32)
    )  # (B,S,H)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))  # (H,)
    log_f = dt * a[None, None, :]
    # single B/C group shared across heads
    k = jnp.broadcast_to(bmat[:, :, None, :], (b, s, heads, state_dim))
    q = jnp.broadcast_to(cmat[:, :, None, :], (b, s, heads, state_dim))
    return q, k, xi, log_f, dt, z, new_hist


def mamba2_block(
    params: Params, x: Array, heads: int, state_dim: int, chunk: int,
    compute_dtype,
) -> Array:
    b, s, d = x.shape
    q, k, v, log_f, dt, z, _ = _mamba_core_inputs(
        params, x, heads, state_dim, compute_dtype
    )
    y, _ = glr_chunked(q, k, v, log_f, dt, chunk=chunk, normalize=False)
    y = y + v * params["d_skip"].astype(compute_dtype)[None, None, :, None]
    y = y.reshape(b, s, -1)
    y = layers.rms_norm(y, params["out_norm"]) * jax.nn.silu(z)
    return y @ params["wd"].astype(compute_dtype)


def mamba2_decode(
    params: Params, x: Array, state: MambaState, heads: int, state_dim: int,
    compute_dtype,
) -> Tuple[Array, MambaState]:
    b = x.shape[0]
    q, k, v, log_f, dt, z, hist = _mamba_core_inputs(
        params, x, heads, state_dim, compute_dtype, conv_history=state.conv
    )
    y, ssm = glr_decode_step(
        q[:, 0], k[:, 0], v[:, 0], log_f[:, 0], dt[:, 0], state.ssm,
        normalize=False,
    )
    y = y + v[:, 0] * params["d_skip"].astype(compute_dtype)[None, :, None]
    y = y.reshape(b, 1, -1)
    y = layers.rms_norm(y, params["out_norm"]) * jax.nn.silu(z)
    return y @ params["wd"].astype(compute_dtype), MambaState(ssm=ssm, conv=hist)


def mamba_state_shape(b: int, d: int, expand: int, state_dim: int, heads: int,
                      conv_width: int):
    d_inner = d * expand
    headdim = d_inner // heads
    return MambaState(
        ssm=RecurrentState(
            s=jnp.zeros((b, heads, state_dim, headdim), jnp.float32),
            n=jnp.zeros((b, heads, state_dim), jnp.float32),
        ),
        conv=jnp.zeros((b, conv_width - 1, d_inner + 2 * state_dim), jnp.float32),
    )
