"""Ring attention — sequence-parallel exact attention over a device mesh.

Long-context support (SURVEY.md §5 "long-context / seq parallel" row;
the task brief's first-class requirement): attention over a sequence too
long for one device's memory, computed EXACTLY by sharding the sequence
axis across the mesh and rotating K/V blocks around the ring with
``jax.lax.ppermute`` while queries stay resident. Each of the P steps
combines one (Q-block, K/V-block) tile with the numerically stable online
softmax (flash-attention-style running max / normalizer / accumulator),
so memory per device is O(T/P · d) while the result is the MATHEMATICALLY
EXACT softmax over the full sequence (no approximation; last-ulp rounding
differs from dense attention because the reduction is reordered), with no
quadratic-in-T buffer anywhere.

Causal runs skip the GEMMs of fully-masked tiles (``lax.cond`` on the
block order). On a synchronous ring this saves energy, not wall — at
step t the busiest device still computes one live tile. The wall fix is
the STRIPED layout (``striped=True``, after Brandon et al.'s Striped
Attention): device i holds the positions congruent to i mod P, so every
(Q-stripe, K-stripe) tile is ~half live and the causal work is balanced
across the ring — no device ever waits on a fully-dead step.
``make_ring_attention(striped=True)`` permutes global arrays to stripes
and back internally; the block form expects stripe-layout inputs.

The memory bound holds for TRAINING too: a ``custom_vjp`` saves only this
device's blocks plus the per-row logsumexp and re-ROTATES K/V around the
ring in the backward pass (flash-attention backward per tile, with the
dK/dV accumulators traveling alongside their blocks until they return
home) — without it, reverse-mode AD through the forward loop would stash
every rotated block as a scan residual and quietly materialize the full
sequence's K/V per device per layer, exactly what ring attention exists
to avoid.

TPU mapping: the tile products are bf16 GEMMs with f32 accumulation on
the MXU (``compute_dtype``); the P-1 forward (P backward) ppermutes ride
the ICI ring, and XLA overlaps each block's GEMM with the next block's
transfer — the compute/communication pipeline of Liu et al.'s ring
attention, expressed in pure ``shard_map`` + collectives rather than
hand-written RDMA.

Public surface:

* :func:`ring_attention_block` — the per-shard computation, for use
  INSIDE an existing ``shard_map`` (composes with other parallelism).
* :func:`make_ring_attention` — wraps it in ``shard_map`` over a named
  mesh axis: ``fn(q, k, v)`` on global ``[T, H, dh]`` arrays.

Parity with dense attention — values AND gradients — is pinned in
``tests/test_ring_attention.py`` on the virtual 8-device mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from jax import shard_map

__all__ = [
    "ring_attention_block", "make_ring_attention", "seq_mesh",
    "stripe_indices",
]

#: additive mask value: large-negative (not -inf) so fully-masked tiles
#: produce exp() underflow to exactly 0 instead of NaN arithmetic
_MASK = -1e30


def seq_mesh(devices=None) -> Mesh:
    """1-D mesh over all devices with a 'seq' axis (the long-context twin
    of — and delegate to — ``parallel.mesh.config_mesh``)."""
    from hpbandster_tpu.parallel.mesh import config_mesh

    return config_mesh(devices, axis_name="seq")


def _ring_perm(p_size):
    return [(s, (s + 1) % p_size) for s in range(p_size)]


def stripe_indices(t: int, p_size: int):
    """Index arrays converting a length-``t`` sequence between natural
    order and the striped layout (device i holds positions ≡ i mod P).

    ``to_striped``: ``x[to_striped]`` is stripe-ordered so a contiguous
    'seq' sharding gives device i slots ``s`` holding position
    ``s * P + i``. ``to_natural`` inverts it."""
    import numpy as np

    assert t % p_size == 0, f"T={t} must divide by the ring size {p_size}"
    b = t // p_size
    n = np.arange(t)
    to_striped = (n % b) * p_size + n // b
    to_natural = np.empty(t, np.int64)
    to_natural[to_striped] = n
    return to_striped, to_natural


def _tile_scores(q_c, k_blk, scale, compute_dtype, causal, striped,
                 i, j, t_q, t_k):
    """[H, Tq, Tk] tile scores: compute_dtype GEMM, f32 accumulation,
    global-position causal mask (contiguous or striped layout)."""
    s = jnp.einsum(
        "qhd,khd->hqk", q_c, k_blk.astype(compute_dtype),
        preferred_element_type=jnp.float32,
    ) * scale
    if causal:
        if striped:
            # striped layout: q position = slot*P + i, k position =
            # slot*P + j, so the tile's causal set is slot_q > slot_k,
            # plus the diagonal when i >= j — every tile is ~half live
            # (the load-balance property)
            sq = jnp.arange(t_q)[:, None]
            sk = jnp.arange(t_k)[None, :]
            live = (sq > sk) | ((sq == sk) & (i >= j))
        else:
            q_pos = i * t_q + jnp.arange(t_q)
            k_pos = j * t_k + jnp.arange(t_k)
            live = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(live[None], s, _MASK)
    return s


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _ring_attention(axis_name, causal, striped, scale, compute_dtype,
                    q, k, v):
    out, _ = _ring_attention_fwd(axis_name, causal, striped, scale,
                                 compute_dtype, q, k, v)
    return out


def _ring_attention_fwd(axis_name, causal, striped, scale, compute_dtype,
                        q, k, v):
    p_size = jax.lax.psum(1, axis_name)
    i = jax.lax.axis_index(axis_name)
    t_q, n_heads, dh = q.shape
    t_k = k.shape[0]
    q_c = q.astype(compute_dtype)
    perm = _ring_perm(p_size)

    def tile_update(j, k_blk, v_blk, m, l, acc):
        """Fold one (Q-block, K/V-block-from-device-j) tile into the
        running online-softmax state."""
        s = _tile_scores(q_c, k_blk, scale, compute_dtype, causal,
                         striped, i, j, t_q, t_k)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hqk,khd->hqd", p.astype(compute_dtype),
            v_blk.astype(compute_dtype),
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    # step 0 (this device's own block) is hoisted: the loop then
    # rotates-then-computes, so exactly P-1 ppermutes ride the ring and
    # no final rotation's result is thrown away. Hoisting also seeds the
    # running max from the never-fully-masked diagonal block, and the
    # q/k/v-derived state is naturally device-varying (what shard_map
    # requires of the carry).
    m0 = jnp.full((n_heads, t_q), _MASK, jnp.float32)
    l0 = jnp.zeros((n_heads, t_q), jnp.float32)
    acc0 = jnp.zeros((n_heads, t_q, dh), jnp.float32)
    m, l, acc = tile_update(i, k, v, m0, l0, acc0)

    def body(t, carry):
        k_blk, v_blk, m, l, acc = carry
        # rotate K/V one hop; XLA overlaps this ICI transfer with the
        # tile GEMMs (the ring-attention pipeline)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        j = (i - t) % p_size  # ring origin after t rotations
        if causal and not striped:
            # a tile whose every key position exceeds every query position
            # is fully masked: its probabilities are exactly 0, so skip
            # its GEMMs (under vmap cond lowers to select and computes
            # both — harmless, just no saving). Striped tiles are ~half
            # live by construction — nothing to skip.
            m, l, acc = jax.lax.cond(
                j * t_k > i * t_q + (t_q - 1),
                lambda: (m, l, acc),
                lambda: tile_update(j, k_blk, v_blk, m, l, acc),
            )
        else:
            m, l, acc = tile_update(j, k_blk, v_blk, m, l, acc)
        return k_blk, v_blk, m, l, acc

    _, _, m, l, acc = jax.lax.fori_loop(
        1, p_size, body, (k, v, m, l, acc)
    )
    out_hqd = acc / l[..., None]
    out = out_hqd.transpose(1, 0, 2).astype(q.dtype)
    # residuals are O(T/P · d): own blocks + per-row logsumexp. The
    # rotated blocks are NOT saved — the backward re-rotates them.
    logsumexp = m + jnp.log(l)
    return out, (q, k, v, out, logsumexp)


def _ring_attention_bwd(axis_name, causal, striped, scale, compute_dtype,
                        res, dout):
    """Flash-attention backward per tile, K/V re-rotated around the ring.

    With the saved logsumexp L the softmax probabilities of any tile are
    recomputable exactly (``p = exp(s - L)``); the dK/dV accumulators
    travel WITH their blocks so after P-1 in-loop rotations plus one
    final hop every block's gradient lands back on its home device.
    """
    q, k, v, out, logsumexp = res
    p_size = jax.lax.psum(1, axis_name)
    i = jax.lax.axis_index(axis_name)
    t_q, n_heads, dh = q.shape
    t_k = k.shape[0]
    q_c = q.astype(compute_dtype)
    perm = _ring_perm(p_size)

    do_f = dout.astype(jnp.float32)
    # D = rowsum(dO ∘ O): the softmax-jacobian correction term, [H, Tq]
    d_corr = jnp.einsum("qhd,qhd->hq", do_f, out.astype(jnp.float32))
    do_c = dout.astype(compute_dtype)

    def tile_grads(j, k_blk, v_blk, dk_blk, dv_blk, dq):
        s = _tile_scores(q_c, k_blk, scale, compute_dtype, causal,
                         striped, i, j, t_q, t_k)
        # exact probabilities; masked entries underflow to exactly 0, so
        # no explicit backward mask is needed
        p = jnp.exp(s - logsumexp[..., None])
        p_c = p.astype(compute_dtype)
        dv_blk = dv_blk + jnp.einsum(
            "hqk,qhd->khd", p_c, do_c, preferred_element_type=jnp.float32
        )
        dp = jnp.einsum(
            "qhd,khd->hqk", do_c, v_blk.astype(compute_dtype),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - d_corr[..., None])).astype(compute_dtype)
        dq = dq + scale * jnp.einsum(
            "hqk,khd->qhd", ds, k_blk.astype(compute_dtype),
            preferred_element_type=jnp.float32,
        )
        dk_blk = dk_blk + scale * jnp.einsum(
            "hqk,qhd->khd", ds, q_c, preferred_element_type=jnp.float32
        )
        return dk_blk, dv_blk, dq

    dk0 = jnp.zeros((t_k, n_heads, dh), jnp.float32)
    dv0 = jnp.zeros((t_k, n_heads, dh), jnp.float32)
    dq0 = jnp.zeros((t_q, n_heads, dh), jnp.float32)
    dk_blk, dv_blk, dq = tile_grads(i, k, v, dk0, dv0, dq0)

    def body(t, carry):
        k_blk, v_blk, dk_blk, dv_blk, dq = carry
        # the gradient accumulators rotate WITH their blocks
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        dk_blk = jax.lax.ppermute(dk_blk, axis_name, perm)
        dv_blk = jax.lax.ppermute(dv_blk, axis_name, perm)
        j = (i - t) % p_size
        if causal and not striped:
            # fully-masked tile: p == 0 everywhere, all its gradient
            # contributions are exactly 0 — skip the four GEMMs
            dk_blk, dv_blk, dq = jax.lax.cond(
                j * t_k > i * t_q + (t_q - 1),
                lambda: (dk_blk, dv_blk, dq),
                lambda: tile_grads(j, k_blk, v_blk, dk_blk, dv_blk, dq),
            )
        else:
            dk_blk, dv_blk, dq = tile_grads(
                j, k_blk, v_blk, dk_blk, dv_blk, dq
            )
        return k_blk, v_blk, dk_blk, dv_blk, dq

    _, _, dk_blk, dv_blk, dq = jax.lax.fori_loop(
        1, p_size, body, (k, v, dk_blk, dv_blk, dq)
    )
    # after P-1 in-loop rotations the accumulators hold block (i+1)'s
    # gradients; one final hop returns every block home (identity at P=1)
    dk_blk = jax.lax.ppermute(dk_blk, axis_name, perm)
    dv_blk = jax.lax.ppermute(dv_blk, axis_name, perm)
    return dq.astype(q.dtype), dk_blk.astype(k.dtype), dv_blk.astype(v.dtype)


_ring_attention.defvjp(_ring_attention_fwd, _ring_attention_bwd)


def ring_attention_block(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    compute_dtype=jnp.bfloat16,
    striped: bool = False,
) -> jax.Array:
    """Exact attention for this device's query block; call inside shard_map.

    ``q``/``k``/``v``: this shard's blocks, ``[T_blk, H, dh]``. With
    ``striped=False`` the global sequence is the concatenation over the
    ``axis_name`` ring in axis order; with ``striped=True`` the caller
    has laid positions out in stripes (device i holds positions ≡ i mod
    P — see :func:`stripe_indices`), which balances causal-mask work
    across the ring. Causal masking uses GLOBAL positions either way, so
    the result equals dense causal attention over the full sequence —
    and so do its gradients (the custom VJP re-rotates K/V instead of
    saving residuals, keeping training memory at O(T/P · d) per device).
    """
    scale = float(q.shape[-1] ** -0.5 if scale is None else scale)
    return _ring_attention(axis_name, bool(causal), bool(striped), scale,
                           compute_dtype, q, k, v)


def make_ring_attention(
    mesh: Mesh,
    axis: str = "seq",
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    compute_dtype=jnp.bfloat16,
    striped: bool = False,
):
    """``fn(q, k, v)`` over GLOBAL ``[T, H, dh]`` arrays in natural
    order, sequence axis sharded over ``mesh[axis]``; jittable,
    differentiable, vmappable.

    ``striped=True`` relayouts the inputs to stripes before sharding and
    the output back to natural order — as reshape/transpose (free of
    materialized index constants; XLA lowers them as cheap copies, often
    fused into the sharding), so every device's causal tiles are ~half
    live: the load-balanced schedule for causal long-context work.
    Non-causal calls skip the relayout (nothing to balance; the result
    is identical either way).

    T must divide evenly by the axis size (shard_map's partitioning
    contract — pad the sequence to a multiple, the standard TPU practice
    for static shapes)."""
    spec = PartitionSpec(axis, None, None)
    p_size = int(mesh.shape[axis])
    # non-causal attention has no mask imbalance to balance: the stripe
    # permutations would be pure overhead for a bit-identical result
    striped = bool(striped) and bool(causal)

    def to_stripes(x):
        # natural -> striped is exactly a (b, P) -> (P, b) transpose of
        # the leading axis: new index i*b + s holds position s*P + i.
        # Same relayout as stripe_indices, without baking length-T index
        # constants into the jaxpr (XLA lowers this as a copy, not a
        # gather) — q and k/v may have different lengths; each uses its
        # own block size (the striped mask only needs a shared modulus P)
        t = x.shape[0]
        assert t % p_size == 0, f"T={t} must divide by the ring size"
        return (x.reshape(t // p_size, p_size, *x.shape[1:])
                .swapaxes(0, 1).reshape(x.shape))

    def to_natural(x):
        t = x.shape[0]
        return (x.reshape(p_size, t // p_size, *x.shape[1:])
                .swapaxes(0, 1).reshape(x.shape))

    def fn(q, k, v):
        if striped:
            q, k, v = to_stripes(q), to_stripes(k), to_stripes(v)
        out = shard_map(
            lambda qb, kb, vb: ring_attention_block(
                qb, kb, vb, axis, causal=causal, scale=scale,
                compute_dtype=compute_dtype, striped=striped,
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
        )(q, k, v)
        return to_natural(out) if striped else out

    return fn
