"""Kimi Delta Attention's chunked gated delta rule as Pallas TPU kernels.

A head carries a state ``S ∈ R^{K×V}`` along the sequence::

    S_t = (I − β_t k_t k_tᵀ) Diag(α_t) S_{t−1} + β_t k_t v_tᵀ
    o_t = S_tᵀ q_t                                   S_0 = 0 at a row's start

with a decay ``α_t ∈ (0, 1]^K`` per CHANNEL (``g = log α``, float32) and
``β_t ∈ (0, 1)`` a head and token. The caller hands q and k as the recurrence
reads them (the mixer L2-normalises both and folds the softmax-free scale
into q). Token by token that is ``S`` sequential rank-one updates. The
chunked algorithm does the same sums a chunk of ``C`` tokens at a time, as
matmuls. With ``Γ_t = Σ_{s≤t} g_s`` inside a chunk, the state ``S_0`` the
chunk was handed, and the pairwise products over channels

    M_qk[t, j] = Σ_c q_tc k_jc e^{Γ_tc − Γ_jc}       j ≤ t
    M_kk[t, j] = Σ_c k_tc k_jc e^{Γ_tc − Γ_jc}       j < t,

the delta rule's WY/UT form gives, ``A = diag(β) M_kk`` strictly lower,

    U   = (I + A)^{-1} diag(β) (V − (K ⊙ e^Γ) S_0)  the values each token
                                                    writes, corrected
    O   = (Q ⊙ e^Γ) S_0 + M_qk U
    S_C = Diag(e^{Γ_C}) S_0 + (K ⊙ e^{Γ_C − Γ})ᵀ U.

``(I + A)^{-1}`` is taken by blocks of ``SUB`` tokens: forward substitution
inside every diagonal block at once, the column substitution's float32
arithmetic in the same order, then the blocks joined by products of float32
operands at ``HIGHEST``: ``(I − N)(I + N²) T_d`` at a chunk of 64, ``T_d``
the blocks' inverse and ``N = T_d L``, L being A below the blocks.

Numerics: ``e^{Γ_t − Γ_j} ≤ 1`` for ``j ≤ t``, but split as ``e^{Γ_t} ·
e^{−Γ_j}`` from the chunk's start it overflows float32 once a channel has
decayed past ``e^{−88}`` inside the chunk. So the pairs are taken by
sub-chunks of ``SUB`` tokens (as FLA's KDA does). A key in an earlier
sub-chunk than its query is two factors from the last token before the
query's sub-chunk, ``e^{Γ_t − ref} · e^{ref − Γ_j}``, each at most 1 (one of
them may round to 0 only where the pair itself is below float32's range):
one product in q's dtype a sub-chunk of queries. Inside a sub-chunk the
kernels halve: at level ``m = SUB/2, ..., 1`` each query in the second half
of a block of ``2m`` tokens meets each key of its first half through the
block's reference ``R``, Γ at the first half's last token, as ``(q_t ∘
e^{Γ_t − R}) · (k_j ∘ e^{R − Γ_j})``. Both exponents are at most 0, and
every row takes one factor a level, so a level is a masked product of
float32 operands at ``HIGHEST`` precision for ``M_kk`` and one for
``M_qk``: the float32 sums of a key at a time, in another order. The pair
t = j is ``q_t · k_t``. No factor above 1 is ever formed, however fast a
channel decays; the mixer's counter ``kda_log_decay_min`` reads how fast
they did.

Forward and backward are one Mosaic call each, named ``kda_fwd`` and
``kda_bwd``, under one ``jax.custom_vjp``; the backward is written by hand.
Their grid is ``(batch, head, chunk)``, the chunk axis last and sequential:
the state (forward) or its cotangent (backward, chunks in reverse) lives in
VMEM scratch from chunk to chunk. Each kernel sums its chunk's log-decays
into Γ itself, and the backward hands back g's cotangent, so no
``[chunks, C]`` layout of the decays is made outside. The forward also
writes each chunk's
entering state (float32 ``[B, H, chunks, K, V]``), which the backward reads
to recompute its chunk. Decays, their cumulative sums, the state,
``(I + A)^{-1}`` and the pairs inside a sub-chunk are float32; q, k, v and
the other products' operands multiply in q's dtype with float32
accumulation.

``impl="jnp"`` is the same chunked algorithm in plain ``jax.numpy`` (the
same per-chunk function, a scan over chunks), autodiff its backward: the
path off the TPU and the kernels' oracle beside the token-by-token
recurrence.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tony_tpu.compat import per_shard
from tony_tpu.ops.attention import _interpret, _prec
from tony_tpu.parallel.mesh import BATCH_AXES

MASKED = -1e30      # an exponent whose factor is 0
L2_EPS = 1e-6       # inside the root of q's and k's L2 norms
SUB = 16            # tokens of a sub-chunk: the span of an in-block pair
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
HIGHEST = jax.lax.Precision.HIGHEST


def _f32(x):
    return x.astype(jnp.float32)


def _dot(a, b, dims, prec):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=prec)


def _rows(n: int, width: int = 1):
    return jax.lax.broadcasted_iota(jnp.int32, (n, width), 0)


def _prefix_sums(x, reverse: bool = False):
    """Sums of ``x [C, K]`` down its rows inside the kernel, up to and with
    each row (``reverse``: from each row on): log₂ C steps, each adding the
    rows a power of two away (``pltpu.roll`` along the sublanes)."""
    n = x.shape[0]
    row = _rows(n)
    step = 1
    while step < n:
        if reverse:
            x = x + jnp.where(row < n - step, pltpu.roll(x, n - step, 0), 0.0)
        else:
            x = x + jnp.where(row >= step, pltpu.roll(x, step, 0), 0.0)
        step *= 2
    return x


def _within(q32, k32, gam):
    """The pairs inside each sub-chunk, ``Σ_c x_tc k_jc e^{Γ_tc − Γ_jc}``
    for j ≤ t (x = q, k), as ``[C, C]`` blocks on the diagonal: the
    ``jax.numpy`` path's form, one ``[SUB, SUB, K]`` factor a sub-chunk (a
    kernel takes them by halving levels, ``_pairs``)."""
    n, kd = gam.shape
    subs = n // SUB
    qs, ks, gs = (t.reshape(subs, SUB, kd) for t in (q32, k32, gam))
    pos = jnp.arange(SUB)
    e = jnp.exp(jnp.where((pos[:, None] >= pos[None, :])[None, :, :, None],
                          gs[:, :, None] - gs[:, None], MASKED))
    eye = jnp.eye(subs, dtype=jnp.float32)

    def blocks(x):
        b = jnp.einsum("stc,sjc,stjc->stj", x, ks, e)
        return jnp.einsum("stj,su->stuj", b, eye).reshape(n, n)
    return blocks(qs), blocks(ks)


def _at_ref(gam, m):
    """Γ at each row's level-``m`` reference, the last row of the first half
    of its block of ``2m`` rows. A block of whole (8, 128) tiles is a
    sublane broadcast; a smaller one takes the row from 1 − m to m rows
    away by rolls."""
    n, kd = gam.shape
    if 2 * m >= 8:
        blocks = gam.reshape(n // (2 * m), 2 * m, kd)
        return jnp.broadcast_to(blocks[:, m - 1:m], blocks.shape).reshape(
            n, kd)
    at = _rows(n) & (2 * m - 1)
    ref = gam
    for s in range(1 - m, m + 1):                 # row r takes row r − s
        if s:
            ref = jnp.where(at == m - 1 + s, pltpu.roll(gam, s % n, 0), ref)
    return ref


def _to_ref(c, m):
    """``_at_ref``'s transpose: each block's sum of ``c`` on its reference
    row, zero on the others."""
    n, kd = c.shape
    at = _rows(n) & (2 * m - 1)
    if 2 * m >= 8:
        blocks = c.reshape(n // (2 * m), 2 * m, kd)
        total = jnp.broadcast_to(jnp.sum(blocks, axis=1, keepdims=True),
                                 blocks.shape).reshape(n, kd)
        return jnp.where(at == m - 1, total, 0.0)
    out = jnp.where(at == m - 1, c, 0.0)
    for s in range(1 - m, m + 1):
        if s:
            out = out + pltpu.roll(jnp.where(at == m - 1 + s, c, 0.0),
                                   -s % n, 0)
    return out


def _pairs(q32, k32, gam, dtype, prec, kernel: bool = True):
    """``M_qk`` (j ≤ t) and ``M_kk`` (j < t) of a chunk, and what their
    backward reuses; entries above those are the caller's to mask. A pair
    whose key lies in an earlier sub-chunk is two factors, each at most 1,
    from the last token before the query's sub-chunk (``ref_i``): ``(q ∘
    e^{Γ − ref_i}) (k ∘ e^{ref_i − Γ})ᵀ``, a product in ``dtype``. A pair
    inside one sub-chunk is float32 throughout: in a ``kernel`` by halving
    levels, else by ``_within``. At level ``m`` (``SUB/2``, ..., 1) a query
    in the second half of a block of ``2m`` rows meets each key of the
    first half through the block's reference ``R``, Γ at the first half's
    last row: ``(q_t ∘ e^{Γ_t − R}) · (k_j ∘ e^{R − Γ_j})``, both exponents
    at most 0. Each row takes one of the two a level as its factor ``f``,
    so a level is two masked products of float32 operands at ``HIGHEST``,
    ``(k ∘ f)(k ∘ f)ᵀ`` and ``(q ∘ f)(k ∘ f)ᵀ``. The pair t = j is ``q_t ·
    k_t``."""
    n, kd = gam.shape
    row = _rows(n)
    subs = [(row >= i * SUB) & (row < (i + 1) * SUB)
            for i in range(n // SUB)]
    refs = [jnp.zeros((1, kd), jnp.float32)] + [
        gam[i * SUB - 1:i * SUB] for i in range(1, n // SUB)]
    gref = refs[0]
    for ref, mine in zip(refs[1:], subs[1:]):
        gref = jnp.where(mine, ref, gref)
    a = jnp.exp(gam - gref)                        # e^{Γ_t − ref(t)} ≤ 1
    qa, ka = (q32 * a).astype(dtype), (k32 * a).astype(dtype)
    kbs, ebs = [None], [None]
    for i in range(1, n // SUB):
        # the keys of the sub-chunks before i, each e^{ref_i − Γ_j} ≤ 1
        eb = jnp.exp(jnp.where(row < i * SUB, refs[i] - gam, MASKED))
        kbs.append((k32 * eb).astype(dtype))
        ebs.append(eb)
    p = dict(a=a, qa=qa, ka=ka, kbs=kbs, ebs=ebs, subs=subs)

    def between(x):
        out = jnp.zeros((n, n), jnp.float32)
        for i in range(1, n // SUB):
            out = jnp.where(subs[i], _dot(x, kbs[i], ((1,), (1,)), prec), out)
        return out

    if not kernel:
        wqk, wkk = _within(q32, k32, gam)
        return between(qa) + wqk, between(ka) + wkk, p
    # M_kk's products are written first: (I + A)^{-1} waits on M_kk alone,
    # M_qk only the output, and a kernel's products reach the MXUs in the
    # order they are written. Its levels come before the pairs between
    # sub-chunks, which only the joins of ``_inverse``'s blocks read. t XOR j
    # is below SUB inside a sub-chunk, below 2m inside a level-m block
    apart = _rows(n, n) ^ jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    mkk = jnp.zeros((n, n), jnp.float32)
    p["levels"] = []
    m = SUB // 2
    while m:
        ref = _at_ref(gam, m)
        f = jnp.exp(jnp.where((row & m) != 0, gam - ref, ref - gam))
        x = jnp.concatenate([q32 * f, k32 * f])
        mkk = jnp.where(apart < 2 * m, _dot(x[n:], x[n:], ((1,), (1,)),
                                            HIGHEST), mkk)
        p["levels"].append((m, f, x))
        m //= 2
    mkk = jnp.where(apart < SUB, mkk, between(ka))
    mqk = between(qa)
    for m, _, x in p["levels"]:
        mqk = jnp.where(apart < 2 * m, _dot(x[:n], x[n:], ((1,), (1,)),
                                            HIGHEST), mqk)
    mqk = jnp.where(apart == 0, jnp.sum(q32 * k32, axis=1, keepdims=True),
                    mqk)
    return mqk, mkk, p


def _pairs_bwd(q32, k32, gam, dmqk, dmkk, p, prec):
    """The cotangents of q, k and Γ from those of ``M_qk`` and ``M_kk``
    (masked), ``p`` being ``_pairs``' intermediates."""
    n, kd = gam.shape
    dtype = p["qa"].dtype
    dmqk_lo, dmkk_lo = dmqk.astype(dtype), dmkk.astype(dtype)
    dqa = jnp.zeros((n, kd), jnp.float32)
    dka = jnp.zeros((n, kd), jnp.float32)
    dk = jnp.zeros((n, kd), jnp.float32)
    dgam = jnp.zeros((n, kd), jnp.float32)
    drefs = [None]
    row = _rows(n)
    for i in range(1, n // SUB):
        kb, eb, mine = p["kbs"][i], p["ebs"][i], p["subs"][i]
        dq_i = jnp.where(mine, dmqk_lo, jnp.zeros_like(dmqk_lo))
        dk_i = jnp.where(mine, dmkk_lo, jnp.zeros_like(dmkk_lo))
        dqa = dqa + _dot(dq_i, kb, ((1,), (0,)), prec)
        dka = dka + _dot(dk_i, kb, ((1,), (0,)), prec)
        dkb = _dot(dq_i, p["qa"], ((0,), (0,)), prec) \
            + _dot(dk_i, p["ka"], ((0,), (0,)), prec)
        dk = dk + dkb * eb
        # into e^{ref_i − Γ_j}; at j = ref_i's own row the factor is e^0
        # whatever Γ is, so that row's two shares (−through, +dref) are left
        # out rather than cancelled in float32
        through = jnp.where(row == i * SUB - 1, 0.0, dkb * _f32(kb))
        dgam = dgam - through
        drefs.append(jnp.sum(through, axis=0, keepdims=True))
    da_t = dqa * _f32(p["qa"]) + dka * _f32(p["ka"])   # into e^{Γ − ref}
    dgam = dgam + da_t
    dq = dqa * p["a"]
    dk = dk + dka * p["a"]
    for i in range(1, n // SUB):                  # ref_i = Γ at i·SUB − 1
        dref = drefs[i] - jnp.sum(jnp.where(p["subs"][i], da_t, 0.0),
                                  axis=0, keepdims=True)
        dgam = dgam + jnp.where(row == i * SUB - 1, dref, 0.0)
    # the pairs inside each sub-chunk, level by level, one product a level:
    # [[0, dM_qk], [dM_qkᵀ, dM_kkᵀ + dM_kk]] against [qf; kf] gives qf's
    # cotangent (dM_qk against kf) over kf's (dM_qk against qf as a key,
    # dM_kk against kf as a key and as a query). The mask of t XOR j is
    # symmetric, so it masks the transposes as it masks dM
    apart = _rows(n, n) ^ jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    apart = jnp.concatenate([apart, apart], axis=1)
    apart = jnp.concatenate([apart, apart])
    zero = jnp.zeros_like(dmqk)
    lhs = jnp.concatenate([
        jnp.concatenate([zero, dmqk], axis=1),
        jnp.transpose(jnp.concatenate([dmqk, dmkk]))
        + jnp.concatenate([zero, dmkk], axis=1)])
    for m, f, x in p["levels"]:
        d = _dot(jnp.where((apart >= m) & (apart < 2 * m), lhs, 0.0), x,
                 ((1,), (0,)), HIGHEST)
        dqf, dkf = d[:n], d[n:]
        dq = dq + dqf * f
        dk = dk + dkf * f
        # into the exponent ±(Γ − R), and R's share: at R's own row the
        # exponent is 0 whatever Γ is, so that row's two shares are left
        # out rather than cancelled in float32
        ce = dqf * x[:n] + dkf * x[n:]
        c = jnp.where((row & (2 * m - 1)) == m - 1, 0.0,
                      jnp.where((row & m) != 0, -ce, ce))
        dgam = dgam - c + _to_ref(c, m)
    # the pairs t = j: e^0, so they move no Γ
    dii = jnp.sum(jnp.where(apart[:n, :n] == 0, dmqk, 0.0), axis=1,
                  keepdims=True)
    return dq + dii * k32, dk + dii * q32, dgam


def _inverse(a):
    """``(I + a)^{-1}`` of a strictly lower ``a [C, C]`` (float32), C a whole
    number of sub-chunks. First ``T_d``, the inverse of I plus a's diagonal
    blocks of ``SUB``, by forward substitution in every block at once. The
    blocks are held side by side, ``[SUB, C]`` with block b in lanes
    ``SUB·b`` on, so step j takes row j of every block (a sublane broadcast)
    times column j of each block of a across that block's lanes (a lane
    gather made from a alone, before the steps): the column substitution's
    float32 arithmetic in the same order, with no lane broadcast for a step
    to wait on. Then ``(I + a)^{-1} = (I + N)^{-1} T_d`` with ``N = T_d L``,
    L being a below the blocks: N is strictly lower by blocks, so
    ``N^{C/SUB} = 0`` and ``(I + N)^{-1} = (I − N)(I + N²)(I + N⁴)…`` ends
    after ``log₂(C/SUB)`` factors, each a product of float32 operands at
    ``HIGHEST`` over the rows its power of N leaves nonzero."""
    n = a.shape[0]
    blocks = n // SUB
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, n), 1)
    group = lane // SUB
    d = a[:SUB]                     # d[s, SUB·b + c] = a[SUB·b + s, SUB·b + c]
    for b in range(1, blocks):
        d = jnp.where(group == b, a[b * SUB:(b + 1) * SUB], d)
    t = jnp.where(lane - SUB * group == _rows(SUB), 1.0, 0.0)
    for j in range(SUB - 1):
        col = jnp.take_along_axis(d, SUB * group + j, axis=1,
                                  mode="promise_in_bounds")
        t = t - col * t[j:j + 1, :]
    td = jnp.concatenate([jnp.where(group == b, t, 0.0)
                          for b in range(blocks)])

    def times(x, y, zero):          # x y, x's first ``zero`` rows being 0
        return jnp.concatenate([jnp.zeros((zero, n), jnp.float32),
                                _dot(x[zero:], y, ((1,), (0,)), HIGHEST)])

    apart = _rows(n, n) ^ jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    low = jnp.where(apart >= SUB, -a, 0.0)
    t, power = td, 1
    while power < blocks:           # m = (−N)^power
        m = times(td, low, SUB) if power == 1 else times(m, m, power * SUB)
        t = t + times(m, t, power * SUB)
        power *= 2
    return t


def _chunk(q, k, v, gam, beta, s0, outputs: bool = True,
           kernel: bool = True):
    """One chunk of one head, forward: ``q``, ``k [C, K]``, ``v [C, V]`` in
    their dtype, ``gam [C, K]`` (Γ, float32), ``beta [C, 1]``, ``s0 [K, V]``
    float32. Returns every intermediate the backward reuses, and with
    ``outputs`` also ``o`` and ``s1`` (the state the chunk leaves).
    ``kernel``: inside a Mosaic kernel (else the ``jax.numpy`` path)."""
    dtype, prec = q.dtype, _prec(q)
    n = gam.shape[0]
    rows, cols = _rows(n, n), jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    q32, k32 = _f32(q), _f32(k)
    mqk, mkk, p = _pairs(q32, k32, gam, dtype, prec, kernel)
    mqk = jnp.where(rows >= cols, mqk, 0.0)
    mkk = jnp.where(rows > cols, mkk, 0.0)
    t = _inverse(beta * mkk)
    last = gam[n - 1:n]                            # Γ_C  [1, K]
    ek, en = jnp.exp(gam), jnp.exp(last - gam)
    kp, qp = (k32 * ek).astype(dtype), (q32 * ek).astype(dtype)
    kn = (k32 * en).astype(dtype)
    s0_lo = s0.astype(dtype)
    w = _f32(v) - _dot(kp, s0_lo, ((1,), (0,)), prec)
    u = _dot(t.astype(dtype), (beta * w).astype(dtype), ((1,), (0,)), prec)
    u_lo = u.astype(dtype)
    kept = jnp.exp(jnp.transpose(gam)[:, n - 1:n])  # e^{Γ_C} a row of S
    out = {}
    if outputs:
        out["o"] = _dot(qp, s0_lo, ((1,), (0,)), prec) \
            + _dot(mqk.astype(dtype), u_lo, ((1,), (0,)), prec)
        out["s1"] = kept * s0 + _dot(kn, u_lo, ((0,), (0,)), prec)
    return dict(out, pairs=p, mqk=mqk, mkk=mkk, t=t, ek=ek, en=en, kp=kp, qp=qp, kn=kn,
                w=w, u=u, u_lo=u_lo, kept=kept, s0_lo=s0_lo)


def _chunk_bwd(q, k, gam, beta, s0, do, ds1, f):
    """One chunk of one head, backward: the cotangents of ``q``, ``k``,
    ``v``, ``gam``, ``beta`` and ``s0`` from those of ``o`` (``do``) and of
    the state the chunk leaves (``ds1``), ``f`` being ``_chunk``'s
    intermediates."""
    dtype, prec = q.dtype, _prec(q)
    n, kd = gam.shape
    rows, cols = _rows(n, n), jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    row = _rows(n)
    q32, k32 = _f32(q), _f32(k)
    do_lo, ds1_lo = do.astype(dtype), ds1.astype(dtype)
    u_lo, s0_lo = f["u_lo"], f["s0_lo"]
    # o = qp S0 + Mqk U ;  s1 = kept ∘ S0 + knᵀ U
    du = _dot(f["mqk"].astype(dtype), do_lo, ((0,), (0,)), prec) \
        + _dot(f["kn"], ds1_lo, ((1,), (0,)), prec)
    dmqk = jnp.where(rows >= cols, _dot(do_lo, u_lo, ((1,), (1,)), prec),
                     0.0)
    dqp = _dot(do_lo, s0_lo, ((1,), (1,)), prec)
    dkn = _dot(u_lo, ds1_lo, ((1,), (1,)), prec)
    ds0 = _dot(f["qp"], do_lo, ((0,), (0,)), prec) + f["kept"] * ds1
    # e^{Γ_C}'s share: Σ_v ds1 ∘ S0 a channel, as a row
    dlast = jnp.transpose(jnp.broadcast_to(
        jnp.sum(ds1 * s0, axis=1, keepdims=True) * f["kept"],
        (kd, kd)))[0:1]
    # U = T (β ∘ W), T = (I + A)^{-1}:  R = Tᵀ dU,  dA = −R Uᵀ
    r = _dot(f["t"].astype(dtype), du.astype(dtype), ((0,), (0,)), prec)
    dw = beta * r
    dbeta = jnp.sum(r * f["w"], axis=1, keepdims=True)
    da = jnp.where(rows > cols,
                   -_dot(r.astype(dtype), u_lo, ((1,), (1,)), prec), 0.0)
    dmkk = beta * da
    dbeta = dbeta + jnp.sum(da * f["mkk"], axis=1, keepdims=True)
    dv = dw
    dw_lo = dw.astype(dtype)
    dkp = -_dot(dw_lo, s0_lo, ((1,), (1,)), prec)
    ds0 = ds0 - _dot(f["kp"], dw_lo, ((0,), (0,)), prec)
    dq, dk, dgam = _pairs_bwd(q32, k32, gam, dmqk, dmkk, f["pairs"], prec)
    # ek = e^Γ (qp, kp) and en = e^{Γ_C − Γ} (kn)
    dq = dq + dqp * f["ek"]
    dk = dk + dkp * f["ek"] + dkn * f["en"]
    # e^{Γ_C − Γ_t} is 1 at the chunk's last row whatever Γ_C is
    through_n = jnp.where(row == n - 1, 0.0, dkn * _f32(f["kn"]))
    dgam = dgam + dqp * _f32(f["qp"]) + dkp * _f32(f["kp"]) - through_n
    dlast = dlast + jnp.sum(through_n, axis=0, keepdims=True)
    dgam = dgam + jnp.where(row == n - 1, dlast, 0.0)
    return dq, dk, dv, dgam, dbeta, ds0


# ---------------------------------------------------------------------------
# The Mosaic calls
# ---------------------------------------------------------------------------
def _softplus(x):
    """log(1 + e^x) with no overflow, in ops the kernel lowers."""
    return jnp.maximum(x, 0.0) + jnp.log(1.0 + jnp.exp(-jnp.abs(x)))


def _unit(x):
    """(x / √(Σx² + eps) a row, and 1 / √(Σx² + eps)) in float32."""
    r = jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=1, keepdims=True) + L2_EPS)
    return x * r, r


def _unit_bwd(u, r, du):
    """The cotangent of x in ``u = _unit(x)[0]`` from that of u."""
    return r * (du - u * jnp.sum(u * du, axis=1, keepdims=True))


def _inputs(q_ref, k_ref, x_ref, a_ref):
    """A grid step's q and k as the recurrence reads them (L2 a row, q
    scaled by K^−½; float32 and in q's dtype), its log-decays g = a ·
    softplus(x) and their sums Γ down the chunk."""
    dtype = q_ref.dtype
    qu, rq = _unit(_f32(q_ref[0]))
    ku, rk = _unit(_f32(k_ref[0]))
    scale = qu.shape[1] ** -0.5
    x = x_ref[0]
    g = a_ref[...] * _softplus(x)
    return dict(q=(qu * scale).astype(dtype), k=ku.astype(dtype), qu=qu,
                rq=rq, ku=ku, rk=rk, scale=scale, x=x, gam=_prefix_sums(g))


def _fwd_kernel(q_ref, k_ref, v_ref, x_ref, a_ref, b_ref, o_ref, s_in_ref,
                state):
    @pl.when(pl.program_id(2) == 0)
    def _row_start():
        state[...] = jnp.zeros_like(state)

    s0 = state[...]
    s_in_ref[0, 0, 0] = s0
    i = _inputs(q_ref, k_ref, x_ref, a_ref)
    f = _chunk(i["q"], i["k"], v_ref[0], i["gam"], b_ref[0, 0], s0)
    o_ref[0] = f["o"].astype(o_ref.dtype)
    state[...] = f["s1"]


def _bwd_kernel(q_ref, k_ref, v_ref, x_ref, a_ref, b_ref, s_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dx_ref, da_ref, db_ref, dstate):
    @pl.when(pl.program_id(2) == 0)     # a row's last chunk: nothing follows
    def _row_end():
        dstate[...] = jnp.zeros_like(dstate)

    i = _inputs(q_ref, k_ref, x_ref, a_ref)
    beta, s0 = b_ref[0, 0], s_ref[0, 0, 0]
    f = _chunk(i["q"], i["k"], v_ref[0], i["gam"], beta, s0, outputs=False)
    dq, dk, dv, dgam, db, ds0 = _chunk_bwd(i["q"], i["k"], i["gam"], beta,
                                           s0, do_ref[0], dstate[...], f)
    dq_ref[0] = _unit_bwd(i["qu"], i["rq"], dq * i["scale"]).astype(
        dq_ref.dtype)
    dk_ref[0] = _unit_bwd(i["ku"], i["rk"], dk).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    dg = _prefix_sums(dgam, reverse=True)         # Γ's cotangent into g's
    x = i["x"]
    dx_ref[0] = dg * a_ref[...] / (1.0 + jnp.exp(-x))   # softplus' = σ
    da_ref[0, 0] = jnp.sum(dg * _softplus(x), axis=0, keepdims=True)
    db_ref[0, 0] = db
    dstate[...] = ds0


def _call(kernel, name: str, chunk: int, heads: int, kd: int, vd: int,
          reverse: bool, operands: str, results):
    """The ``pallas_call`` of ``kernel`` on the grid ``(batch, head,
    chunk)``, chunks in ``reverse`` for the backward. ``operands`` names each
    operand's block, ``results`` each result's ``(block, dtype)``: ``k`` a
    head's ``K`` columns of a chunk ``[1, C, K]``, ``v`` its ``V`` columns,
    ``b`` its β ``[1, 1, C, 1]``, ``s`` the state a chunk was handed ``[1,
    1, 1, K, V]``, ``a`` the head's decay rate on each of its lanes ``[1,
    K]``, ``p`` a chunk's sums down its rows ``[1, 1, 1, K]``."""
    def run(*args):
        batch, seq = args[0].shape[:2]
        chunks = seq // chunk

        def at(c):
            return chunks - 1 - c if reverse else c

        spec = {
            "k": pl.BlockSpec((1, chunk, kd), lambda b, h, c: (b, at(c), h)),
            "v": pl.BlockSpec((1, chunk, vd), lambda b, h, c: (b, at(c), h)),
            "b": pl.BlockSpec((1, 1, chunk, 1),
                              lambda b, h, c: (b, h, at(c), 0)),
            "s": pl.BlockSpec((1, 1, 1, kd, vd),
                              lambda b, h, c: (b, h, at(c), 0, 0)),
            "a": pl.BlockSpec((1, kd), lambda b, h, c: (0, h)),
            "p": pl.BlockSpec((1, 1, 1, kd),
                              lambda b, h, c: (b, at(c), 0, h)),
        }
        shape = {"k": (batch, seq, heads * kd), "v": (batch, seq, heads * vd),
                 "b": (batch, heads, seq, 1),
                 "s": (batch, heads, chunks, kd, vd),
                 "a": (1, heads * kd), "p": (batch, chunks, 1, heads * kd)}
        return pl.pallas_call(
            kernel, grid=(batch, heads, chunks),
            in_specs=[spec[o] for o in operands],
            out_specs=[spec[r] for r, _ in results],
            out_shape=[jax.ShapeDtypeStruct(shape[r], dtype or args[0].dtype)
                       for r, dtype in results],
            scratch_shapes=[pltpu.VMEM((kd, vd), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=_interpret(), name=name)(*args)

    return run


# Each call is a jit inlined where it is called: its kernel, unrolled in
# Python, is traced once for all the calls of one shape (a step has a layer's
# forward, its recompute and its backward in every KDA layer), and the
# identical kernels lower once. The calls keep their callers' scopes.
@functools.partial(jax.jit, static_argnames=("chunk", "heads"), inline=True)
def _fwd_call(q, k, v, x, a, beta, *, chunk, heads):
    """``q``, ``k [B, S, H·K]`` as the mixer made them, ``v [B, S, H·V]``,
    ``x [B, S, H·K]`` float32 (the decay's pre-activation), ``a [1, H·K]``
    (each head's rate on its lanes), ``beta [B, H, S, 1]`` float32 → ``o
    [B, S, H·V]`` and each chunk's entering state."""
    kd, vd = q.shape[2] // heads, v.shape[2] // heads
    return _call(_fwd_kernel, "kda_fwd", chunk, heads, kd, vd, False,
                 "kkvkab", [("v", None), ("s", jnp.float32)])(
                     q, k, v, x, a, beta)


@functools.partial(jax.jit, static_argnames=("chunk", "heads"), inline=True)
def _bwd_call(q, k, v, x, a, beta, s_in, do, *, chunk, heads):
    """The cotangents of ``_fwd_call``'s operands from ``do``; ``a``'s as
    each chunk's sums a lane, for the caller to add up."""
    kd, vd = q.shape[2] // heads, v.shape[2] // heads
    return _call(_bwd_kernel, "kda_bwd", chunk, heads, kd, vd, True,
                 "kkvkabsv", [("k", None), ("k", None), ("v", v.dtype),
                              ("k", jnp.float32), ("p", jnp.float32),
                              ("b", jnp.float32)])(
                                  q, k, v, x, a, beta, s_in, do)


def _shard(fn, chunk, heads):
    """``fn`` on each device's rows; ``a`` (leading dim 1) is every
    device's whole."""
    def run(q, k, v, x, a, beta, *rest):
        return per_shard(
            lambda q, k, v, x, beta, *rest: fn(
                q, k, v, x, a, beta, *rest, chunk=chunk, heads=heads),
            _ROWS)(q, k, v, x, beta, *rest)
    return run


# Every operand's leading dim but ``a``'s is the batch: under a bound mesh
# each device runs the kernels on its own rows (compat.per_shard).
_ROWS = (BATCH_AXES,)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(q, k, v, x, a, beta, chunk, heads):
    return _shard(_fwd_call, chunk, heads)(q, k, v, x, a, beta)[0]


def _scan_fwd(q, k, v, x, a, beta, chunk, heads):
    o, s_in = _shard(_fwd_call, chunk, heads)(q, k, v, x, a, beta)
    return o, (q, k, v, x, a, beta, s_in)


def _scan_bwd(chunk, heads, res, do):
    dq, dk, dv, dx, da, db = _shard(_bwd_call, chunk, heads)(*res, do)
    return dq, dk, dv, dx, jnp.sum(da, axis=(0, 1)), db


_scan.defvjp(_scan_fwd, _scan_bwd)


def _kernels(q, k, v, x, a, beta, chunk: int):
    b, s, h, kd = q.shape
    vd = v.shape[3]
    o = _scan(q.reshape(b, s, h * kd), k.reshape(b, s, h * kd),
              v.reshape(b, s, h * vd), _f32(x).reshape(b, s, h * kd),
              jnp.repeat(_f32(a), kd)[None],
              _f32(beta).transpose(0, 2, 1)[..., None], chunk, h)
    return o.reshape(b, s, h, vd)


def log_decays(x, a):
    """g = a_h · softplus(x): ``x [B, S, H, K]``, ``a [H]``."""
    return _f32(a)[:, None] * jax.nn.softplus(_f32(x))


def normed(q, k):
    """q and k as the recurrence reads them: L2 a head, q times K^−½."""
    kd = q.shape[-1]

    def unit(t):
        t = _f32(t)
        return t * jax.lax.rsqrt(jnp.sum(jnp.square(t), axis=-1,
                                         keepdims=True) + L2_EPS)
    return (unit(q) * kd ** -0.5).astype(q.dtype), unit(k).astype(k.dtype)


def _chunked(q, k, v, x, a, beta, chunk: int):
    """The kernels' algorithm in plain ``jax.numpy``: ``_chunk`` for every
    row and head of a chunk at once, a scan over the chunks carrying the
    state."""
    q, k = normed(q, k)
    g = log_decays(x, a)
    b, s, h, kd = q.shape
    vd, n = v.shape[3], s // chunk

    def split(x):       # [B, S, H, ...] -> [chunks, B, H, C, ...]
        x = x.reshape(b, n, chunk, h, *x.shape[3:])
        return jnp.moveaxis(x, (1, 3), (0, 2))

    one = jax.vmap(jax.vmap(lambda *a: (lambda f: (f["o"], f["s1"]))(
        _chunk(*a, kernel=False))))

    def step(state, xs):
        o, state = one(*xs, state)
        return state, o

    _, o = jax.lax.scan(step, jnp.zeros((b, h, kd, vd), jnp.float32), (
        split(q), split(k), split(v), split(jnp.cumsum(
            g.reshape(b, n, chunk, h, kd), axis=2).reshape(b, s, h, kd)),
        split(_f32(beta)[..., None])))
    return jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, s, h, vd).astype(
        v.dtype)


def kda(q, k, v, x, a, beta, chunk: int = 64, impl: Optional[str] = None):
    """``o [B, S, H, V]`` of the recurrence above for ``q``, ``k [B, S, H,
    K]`` before their L2 norms (each is normalised a head, q also scaled by
    ``K^−½``), ``v [B, S, H, V]``, the log-decays ``g = a_h · softplus(x)``
    from ``x [B, S, H, K]`` and ``a [H]`` (at most 0; ``log_decays``), and
    ``beta [B, S, H]``. The norms and the decay gate are taken inside the
    kernels, so the only per-channel float32 input is ``x``. Every row
    starts from a zero state. ``S`` must be a whole number of chunks, and a
    chunk a whole number of sub-chunks of ``SUB``. ``impl``: ``"kernel"``
    (the Mosaic calls; interpreted off the TPU), ``"jnp"``, or None for the
    kernels on a TPU and ``jax.numpy`` elsewhere."""
    if q.shape[1] % chunk or chunk % SUB:
        raise ValueError(f"a sequence of {q.shape[1]} in chunks of {chunk} "
                         f"of sub-chunks of {SUB}")
    if k.shape != q.shape or x.shape != q.shape or a.shape != q.shape[2:3] \
            or beta.shape != q.shape[:3] or v.shape[:3] != q.shape[:3]:
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape}, x "
                         f"{x.shape}, a {a.shape}, beta {beta.shape}")
    if impl is None:
        impl = "jnp" if _interpret() else "kernel"
    if impl not in ("kernel", "jnp"):
        raise ValueError(f"impl {impl!r} is neither 'kernel' nor 'jnp'")
    return (_kernels if impl == "kernel" else _chunked)(q, k, v, x, a, beta,
                                                        chunk)
