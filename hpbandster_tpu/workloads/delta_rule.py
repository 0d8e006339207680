"""The gated delta rule, chunk by chunk: the one scan of the linear-attention
lanes (``kimi_linear.py``'s KDA, ``olmo_hybrid.py``'s Gated DeltaNet).

Per head, with ``S`` f32[d_k, d_v] zero at the start::

    S_t = (I - beta_t k_t k_t^T) diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

The gate ``a_t`` comes in two forms, told apart by the shape of ``log_a``:
**a gate a channel** (KDA: ``log_a`` f32[T, H, d_k]) and **a gate a head**
(Gated DeltaNet: ``log_a`` f32[T, H], ``diag(a_t) = a_t I``). What differs
between them is the part of a chunk that needs no state
(:func:`_chunk_local`): with a gate a channel a chunk's ``A_ij = sum_c k_ic
k_jc exp(G_ic - G_jc)`` is summed channel by channel (:func:`_chunk_products`:
blocks on the diagonal one decay at a time, blocks under it split at ``g*``);
with a gate a head it is ``(K K^T)_ij exp(G_i - G_j)``, one product and a
``C x C`` mask (:func:`_head_products`). The solve against ``[V, K exp G]``,
the recurrence of the state over chunks and the backward rule
(:func:`_chunks_backward`, whose ``jax.vjp`` of the chunk-local part picks
the form up with it) are shared. ``d_v`` is read from ``v``: ``d_k`` and
``d_v`` need not be equal.

**The solve is products** (:func:`_inverse_and_solved`): a chunk's system is
``I + N`` with ``N`` strictly lower, and its inverse the doubled block
inverse of ``ops/pallas_triangular.py`` (``[[X, 0], [-Z Y X, Z]]`` from blocks
of one row up: forward substitution's arithmetic in another order, whatever
the chunk's length), in one kernel that holds a tile of systems in VMEM where
Mosaic compiles it and the shapes fit, as plain batched products elsewhere.
The forward keeps the inverse beside the solved rows, and the backward's
transposed solve is one product with it. Every product that makes or
applies the inverse, in the kernel, in the plain form and in the backward,
has float32 operands and sums (:data:`_EXACT`): the solved rows and their
pull-back stand where ``solve_triangular``'s stood, at float32's rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from hpbandster_tpu.ops import pallas_triangular
from hpbandster_tpu.workloads import lane
from hpbandster_tpu.workloads.lane import _FLOAT32, _einsum

__all__ = ["delta_rule_chunked", "solve_counters"]

#: the products that make or apply a system's inverse: six bfloat16 passes a
#: float32 product on the chip (``lane._FLOAT32``'s three leave the solved rows
#: and ``d rhs`` 60 to 90 times further from float64 than substitution does)
_EXACT = jax.lax.Precision.HIGHEST


def _init_leaf(key, name: str, shape, init_scale):
    """The lane's draw of a leaf (``lane._init_leaf``), and the gate's two
    leaves that are not drawn: ``A_log`` the log of 1..16 over the heads,
    ``dt_bias`` the inverse softplus of 0.001..0.1 (geometric) over its
    entries (KDA's channels, Gated DeltaNet's heads). ``key`` is passed on
    as it came: a PRNG key, or ``lane.Init``'s ``draw(name, shape)``."""
    leaf = name.rsplit("/", 1)[-1]
    if leaf == "A_log":
        return jnp.log(jnp.linspace(1.0, 16.0, shape[0], dtype=jnp.float32))
    if leaf == "dt_bias":
        dt = jnp.exp(jnp.linspace(
            np.log(0.001), np.log(0.1), shape[0], dtype=jnp.float32))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
    return lane._init_leaf(key, name, shape, init_scale)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _chunk_products(q, k, g, sub: int):
    """``(A, P)`` f32[..., C, C] of a chunk: ``A_ij = sum_c k_ic k_jc
    exp(g_ic - g_jc)`` and ``P`` with ``q`` on the left, for ``j <= i``
    (zero above the diagonal); ``q, k, g`` f32[..., C, d], ``g`` the running
    sum of ``log a`` (never rising). In blocks of ``sub`` positions: a block
    on the diagonal sums its ``sub x sub x d`` decays one by one; a block
    under it splits the decay at ``g`` of the last position before the row
    block, ``exp(g_i - g*) exp(g* - g_j)``, both exponents never positive,
    and becomes a matrix product."""
    c, d = q.shape[-2:]
    r = c // sub
    blocks = lambda x: x.reshape(x.shape[:-2] + (r, sub, d))
    qb, kb, gb = blocks(q), blocks(k), blocks(g)
    # on the diagonal: [..., r, i, j, d], reduced over d at once
    tri = jnp.tril(jnp.ones((sub, sub), bool))[:, :, None]
    decay = jnp.exp(jnp.where(
        tri, gb[..., :, None, :] - gb[..., None, :, :], -jnp.inf))
    kd = kb[..., None, :, :] * decay
    a_diag = jnp.sum(kb[..., :, None, :] * kd, -1)          # [..., r, i, j]
    p_diag = jnp.sum(qb[..., :, None, :] * kd, -1)
    if r == 1:
        return a_diag[..., 0, :, :], p_diag[..., 0, :, :]
    # under it: g* of row block b is g at the end of block b - 1. Where the
    # decay is split is no one's gradient: exp(g_i - g*) exp(g* - g_j) does
    # not move with g*, and the two sums that would say so are not computed
    g_star = jax.lax.stop_gradient(jnp.concatenate(
        [jnp.zeros_like(gb[..., :1, -1, :]), gb[..., :-1, -1, :]], -2))  # [..., r, d]
    left = jnp.exp(gb - g_star[..., :, None, :])                        # [..., r, i, d]
    below = jnp.tril(jnp.ones((r, r), bool), -1)[:, :, None, None]
    right = kb[..., None, :, :, :] * jnp.exp(jnp.where(                 # [..., b, b', j, d]
        below, g_star[..., :, None, None, :] - gb[..., None, :, :, :], -jnp.inf))
    # the rows of k and of q in one product against all the chunk's columns
    # at once, [..., b, 2 sub, C]: a row of 64 x 64 blocks is then a row of
    # the chunk's matrix as it lies in memory, and (b, i) -> C below moves
    # nothing (blocks of [b, i, b', j] the compiler holds with the chunks in
    # the lanes and copies twice to hand the solve a matrix: 0.8 ms a layer)
    right = right.reshape(right.shape[:-3] + (c, d))
    off = jnp.einsum(
        "...bic,...bjc->...bij", jnp.concatenate([kb * left, qb * left], -2), right,
        precision=_FLOAT32)
    eye = jnp.eye(r, dtype=jnp.float32)[:, None, :, None]               # [b, 1, b', 1]
    whole = lambda diag, off: (
        (diag[..., :, :, None, :] * eye).reshape(diag.shape[:-1] + (c,)) + off
    ).reshape(q.shape[:-2] + (c, c))
    return whole(a_diag, off[..., :sub, :]), whole(p_diag, off[..., sub:, :])


def _head_products(q, k, g):
    """:func:`_chunk_products` where the gate is one number a head and a
    step: ``g`` f32[..., C], ``A_ij = (k_i . k_j) exp(g_i - g_j)`` and ``P``
    with ``q`` on the left, for ``j <= i``: the rows of ``k`` and of ``q``
    against ``k`` as one product (float32 operands: ``A`` feeds the solve),
    times one ``C x C`` array of decays whose exponents are never
    positive. No blocks, nothing split."""
    c = q.shape[-2]
    tri = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(tri, g[..., :, None] - g[..., None, :], -jnp.inf))
    both = jnp.einsum(
        "...ic,...jc->...ij", jnp.concatenate([k, q], -2), k, precision=_FLOAT32)
    return both[..., :c, :] * decay, both[..., c:, :] * decay


def _chunk_local(q, k, v, log_a, beta, sub: int):
    """What of a chunk does not need the state, for all chunks at once
    (``[n, H, C, ...]`` in): ``(system, rhs, P, q exp G, k exp(G_C - G),
    exp G_C)``, the triangular system ``I + diag(beta) tril(A, -1)`` and its
    right-hand side ``diag(beta) [V, K exp G]`` among them. ``log_a`` f32[n,
    H, C, d_k] is a gate a channel; f32[n, H, C] a gate a head: ``G`` then
    scales whole rows, and ``exp G_C`` is f32[n, H, 1, 1]."""
    chunk = q.shape[2]
    per_head = log_a.ndim == 3
    g = jnp.cumsum(log_a, axis=2)
    a, p = _head_products(q, k, g) if per_head else _chunk_products(q, k, g, sub)
    if per_head:
        g = g[..., None]                                 # [n, H, C, 1]: over the row
    strictly = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    system = (jnp.eye(chunk, dtype=jnp.float32)
              + beta[..., None] * jnp.where(strictly, a, 0.0))
    from_start = jnp.exp(g)
    rhs = beta[..., None] * jnp.concatenate([v, k * from_start], -1)
    q_start = q * from_start
    k_end = k * jnp.exp(g[:, :, -1:, :] - g)
    keep = from_start[:, :, -1, :, None]                 # [n, H, dk | 1, 1]
    return system, rhs, p, q_start, k_end, keep


def _solve_in_vmem(systems: int, chunk: int, width: int) -> bool:
    """Whether ``systems`` systems of ``chunk`` rows against right-hand sides
    ``width`` wide go through the kernel (``ops/pallas_triangular.py``: a
    tile of systems in VMEM, the inverse formed there): where Mosaic
    compiles it, on a TPU backend, and the shapes fit its tiles; elsewhere
    the same inverse as plain batched products, which is also what the kernel
    is tested against. The backend and the shapes decide: nothing here reads
    a model."""
    return lane.pallas_available() and pallas_triangular.fits(systems, chunk, width)


def solve_counters(t: int, h: int, dk: int, dv: int, chunk: int):
    """The static fact of how a lane of ``t`` positions and ``h`` heads solves
    its chunks' systems, beside its counted ones: 1 where the kernel does (on
    the chip at the published sizes), 0 where the plain products do."""
    return (("delta_solve_in_vmem",
             float(_solve_in_vmem(-(-t // chunk) * h, chunk, dk + dv))),)


def _inverse_and_solved(system, rhs):
    """``(system^-1, system^-1 rhs)`` of the chunks' unit lower triangular
    systems f32[n, H, C, C] against ``rhs`` f32[n, H, C, R], by products:
    the doubled block inverse (``pallas_triangular.blocked_inverse``), exact
    as forward substitution is, in the kernel or plain
    (:func:`_solve_in_vmem`); the inverse is applied at :data:`_EXACT` either
    way."""
    n, h, chunk, width = rhs.shape
    if _solve_in_vmem(n * h, chunk, width):
        inverse, solved = pallas_triangular.inverse_and_solved(
            system.reshape(n * h, chunk, chunk), rhs.reshape(n * h, chunk, width))
        return inverse.reshape(system.shape), solved.reshape(rhs.shape)
    inverse = pallas_triangular.blocked_inverse(system, chunk)
    return inverse, jnp.matmul(inverse, rhs, precision=_EXACT)


def _solve_pulled_back(inverse, solved, d_solved):
    """``(d system, d rhs)`` of ``solved = system^-1 rhs``: the transposed
    solve is one product with the inverse the forward kept, ``d rhs =
    system^-T d solved`` at :data:`_EXACT` as the forward's, and ``d system =
    -d rhs solved^T`` (what of it lies on or above the diagonal meets a
    constant)."""
    d_rhs = jnp.einsum("nhji,nhjr->nhir", inverse, d_solved, precision=_EXACT)
    return -jnp.einsum("nhiv,nhjv->nhij", d_rhs, solved, precision=_FLOAT32), d_rhs


def _chunks_forward(q, k, v, log_a, beta, sub: int, scope: str, kept: bool):
    """The chunks' outputs f32[n, H, C, d_v] and, where ``kept``, what the
    backward rule reads beside the inputs: the systems' inverses and the
    solved rows (:func:`_inverse_and_solved`), the state every chunk starts
    with and its corrected values ``u``."""
    _, h, chunk, dk = q.shape
    dv = v.shape[-1]
    system, rhs, p, q_start, k_end, keep = _chunk_local(q, k, v, log_a, beta, sub)
    inverse, solved = _inverse_and_solved(system, rhs)
    w_v, w_k = solved[..., :dv], solved[..., dv:]

    def one_chunk(state, xs):
        w_v, rows, p, k_end, keep = xs           # rows: w_k above q_start
        from_state = _einsum("hic,hcv->hiv", rows, state)
        u = w_v - from_state[:, :chunk]
        out = from_state[:, chunk:] + _einsum("hij,hjv->hiv", p, u)
        after = keep * state + _einsum("hic,hiv->hcv", k_end, u)
        return after, ((out, state, u) if kept else out)

    _, out = jax.lax.scan(
        one_chunk, jnp.zeros((h, dk, dv), jnp.float32),
        (w_v, jnp.concatenate([w_k, q_start], 2), p, k_end, keep))
    if not kept:
        return out
    out, starts, u = out
    return out, (q, k, v, log_a, beta, inverse, solved, starts, u)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _delta_chunks(q, k, v, log_a, beta, sub: int, scope: str):
    """:func:`delta_rule_chunked` on whole chunks, ``[n, H, C, ...]`` in and
    out, under a backward rule of its own (:func:`_chunks_backward`)."""
    return _chunks_forward(q, k, v, log_a, beta, sub, scope, kept=False)


def _chunks_backward(sub: int, scope: str, kept, dout):
    """The pull-back of :func:`_delta_chunks`, from the last chunk to the
    first. With ``S0`` the state a chunk starts with and ``dS`` the
    cotangent of the state it ends with (the forward's ``out = q_start S0 +
    P u``, ``u = w_v - w_k S0``, ``S_C = keep * S0 + k_end^T u``)::

        du  = P^T dout + k_end dS            dS0 = [w_k; q_start]^T [-du; dout]
        dP  = dout u^T                             + keep * dS
        dw_v = du          dw_k = -du S0^T         dq_start = dout S0^T
        dk_end = u dS^T    dkeep = sum_v(S0 * dS)

    Only ``du`` and ``dS0`` need the chunk after: they are the scan, two
    products a chunk; the other five are products over all chunks at once,
    before it (``P^T dout``) and after. Then the solve's transpose, one
    product with the inverse that the forward kept
    (:func:`_solve_pulled_back`), and JAX's own pull-back of :func:`_chunk_local`,
    whose inside is computed again here from the inputs, in the form that
    ``log_a``'s shape says (with a gate a head ``dkeep`` is summed over the
    state's rows too). The rule names its own scopes (``scope``: the
    caller's part, ``lane.kda`` or ``lane.gdn``): it is traced where the
    layer's caller has none."""
    q, k, v, log_a, beta, inverse, solved, starts, u = kept
    chunk, dv = q.shape[2], v.shape[-1]
    with jax.named_scope(scope):
        with jax.named_scope("pass.recompute"):
            (_, _, p, q_start, k_end, keep), local_back = jax.vjp(
                functools.partial(_chunk_local, sub=sub), q, k, v, log_a, beta)
        rows = jnp.concatenate([solved[..., dv:], q_start], 2)
        du_own = _einsum("nhij,nhiv->nhjv", p, dout)

        def one_chunk(d_after, xs):
            du_own, dout, rows, k_end, keep = xs
            du = du_own + _einsum("hic,hcv->hiv", k_end, d_after)
            d_start = keep * d_after + _einsum(
                "hic,hiv->hcv", rows, jnp.concatenate([-du, dout], 1))
            return d_start, (du, d_after)

        _, (du, d_after) = jax.lax.scan(
            one_chunk, jnp.zeros_like(starts[0]),
            (du_own, dout, rows, k_end, keep), reverse=True)
        dp = _einsum("nhiv,nhjv->nhij", dout, u)
        d_rows = _einsum("nhiv,nhcv->nhic", jnp.concatenate([-du, dout], 2), starts)
        dk_end = _einsum("nhiv,nhcv->nhic", u, d_after)
        dkeep = jnp.sum(starts * d_after, -1, keepdims=True)
        if keep.shape[-2] == 1:
            dkeep = jnp.sum(dkeep, -2, keepdims=True)
        d_system, d_rhs = _solve_pulled_back(
            inverse, solved, jnp.concatenate([du, d_rows[:, :, :chunk]], -1))
        return local_back((d_system, d_rhs, dp, d_rows[:, :, chunk:], dk_end, dkeep))


_delta_chunks.defvjp(functools.partial(_chunks_forward, kept=True), _chunks_backward)


def delta_rule_chunked(q, k, v, log_a, beta, chunk: int, sub: int = None, *,
                       scope: str):
    """The gated delta rule, chunk by chunk.

    ``q, k`` f32[T, H, d_k], ``v`` f32[T, H, d_v], ``beta`` f32[T, H];
    ``log_a`` (``log a_t <= 0``) f32[T, H, d_k], a gate a channel, or f32[T,
    H], a gate a head; returns f32[T, H, d_v]. Inside a chunk, with ``G_i``
    the running sum of ``log_a`` and ``u_i`` the delta rule's corrected
    values, ``(I + diag(beta) tril(A, -1)) U = diag(beta) (V - (K exp G)
    S_0)`` where ``A_ij = sum_c k_ic k_jc exp(G_ic - G_jc)``: one triangular
    system, inverted by products (:func:`_inverse_and_solved`: exact, as
    substitution is), gives ``U`` from the state the chunk starts with (the WY form),
    then ``o_i = (q_i exp G_i) S_0 + sum_{j<=i} P_ij u_j`` with ``P`` as
    ``A`` with ``q`` on the left, and ``S_C = diag(exp G_C) S_0 + (K exp(G_C
    - G))^T U``. Every exponent is a difference that is never positive, so
    no decay however strong overflows. What does not need the state (``A``,
    ``P``, the solve against ``[V, K exp G]``) is computed for all chunks at
    once (:func:`_chunk_local`; with a gate a channel the products in blocks
    of ``sub``, a quarter of the chunk unless given; with a gate a head one
    masked product each, and ``sub`` is not read); only the state's own
    recurrence, three small products a chunk, is a scan. A length that is no
    multiple of ``chunk`` is padded with steps that leave the state alone
    (``a = 1, beta = 0``).

    Its gradient is a rule of its own (:func:`_chunks_backward`: the scan
    from the last chunk to the first written out, the solve's transpose one
    product with the kept inverse), not what JAX makes of the scan and the
    solve; ``scope`` is the
    caller's part (``lane.kda``, ``lane.gdn``: the ``jax.named_scope`` it
    calls this under), which the rule names again."""
    t, h, _ = q.shape
    sub = sub or max(chunk // 4, 1)
    pad = -t % chunk
    if pad:
        q, k, v, log_a, beta = (
            jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            for x in (q, k, v, log_a, beta))
    n = (t + pad) // chunk
    # chunk-major, head before position: [n, H, C, d]
    split = lambda x: x.reshape((n, chunk) + x.shape[1:]).swapaxes(1, 2)
    out = _delta_chunks(*(split(x) for x in (q, k, v, log_a, beta)), sub, scope)
    return out.swapaxes(1, 2).reshape((t + pad, h, v.shape[-1]))[:t]
