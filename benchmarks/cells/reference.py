"""The plain reference's machinery and the comparison that decides
``correct``.

Everything a cell's verdict rests on that is the same for every architecture
is here and imports nothing of the program: the weights and token ids made
from the seed, AdamW as optax defines it, the first steps followed from the
seed (``follow``), and the gaps that are held against the limits
(``compare``). What the model is, its leaves (``leaf_specs(cfg)``) and its
loss (``loss_fn(cfg, params, tokens)``) in straightforward ``jax.numpy``
float32, is the architecture's to say, in
``architectures/<model_type>/reference.py`` (``arch.py``); ``follow``,
``make_params`` and ``change_norms`` take that module as ``model``. No
architecture is named in this code. Matmuls run at ``highest`` precision: no
kernels, no bf16, no remat policy of the program's.

An architecture's reference may build its loss from the plain blocks below
(``rmsnorm``, ``rope``, ``attention``, ``by_position_blocks``, ``blocks``,
``next_token_nll_sum``, ``mean_over_rows``) or from its own. RoPE is the
half-split (``rotate_half``) form.

The reference runs on the device after the window has closed and the
program's state is freed. So that it fits beside its own Adam state it works
in blocks: rows one at a time, attention one kv-head and one block of queries
at a time, per-position work and the logits a block of positions at a time,
each block recomputed in the backward pass. The blocks change where
temporaries live, not one operation of the mathematics.
"""

from __future__ import annotations

import math
import statistics

import jax
import jax.numpy as jnp
import numpy as np

ATTN_Q_BLOCK = 512      # queries per attention block
POS_BLOCK = 2048        # positions per block of by_position_blocks, logits
# A leaf whose reference gradient norm is under this share of the median
# leaf's moves under Adam by round-off alone and is left out of the change.
DEAD_LEAF_SHARE = 1e-3


# ---------------------------------------------------------------------------
# Inputs from the seed
# ---------------------------------------------------------------------------
def seed_key(seed: int) -> jax.Array:
    """The seed as a key. It is an argument of every jitted function below,
    never a constant inside one, so that one compiled program serves every
    seed. --seed may need more than 32 signed bits; both halves fold in."""
    key = jax.random.key(int(seed) & 0x7FFFFFFF)
    return jax.random.fold_in(key, int(seed) >> 31)


def make_leaf(model, cfg: dict, key: jax.Array, index: int) -> jax.Array:
    """Leaf ``index`` of ``model.leaf_specs(cfg)``, which is ``[(path, shape,
    std)]`` in the sorted order of the program's parameter tree, in float32:
    normal(0, std) from the seed's key and the leaf's index, or ones where
    ``std`` is None."""
    _, shape, std = model.leaf_specs(cfg)[index]
    if std is None:
        return jnp.ones(shape, jnp.float32)
    return jax.random.normal(jax.random.fold_in(key, index), shape,
                             jnp.float32) * jnp.float32(std)


def make_tree(model, cfg: dict, leaf) -> dict:
    """Nested dicts in the program's layout, ``leaf(index, shape)`` at each
    leaf."""
    tree: dict = {}
    for i, (path, shape, _) in enumerate(model.leaf_specs(cfg)):
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf(i, shape)
    return tree


def make_params(model, cfg: dict, key: jax.Array) -> dict:
    """The whole parameter tree from the seed's key."""
    return make_tree(model, cfg,
                     lambda i, shape: make_leaf(model, cfg, key, i))


def flat(tree: dict) -> list:
    """Leaves in ``leaf_specs`` order."""
    return [x for _, x in sorted(
        ((tuple(str(getattr(k, "key", k)) for k in p), x) for p, x in
         jax.tree_util.tree_leaves_with_path(tree)), key=lambda t: t[0])]


def token_rows(seed: int, step: int, batch: int, seq: int,
               vocab: int) -> np.ndarray:
    """Ids of step ``step``: row ``r`` is a pure function of (seed, step, r).
    A copy of what ``data.synthetic_lm_batches`` documents; the run checks
    that what was fed equals it."""
    out = np.empty((batch, seq), np.int32)
    for r in range(batch):
        rng = np.random.default_rng(np.random.SeedSequence([seed, step, r]))
        out[r] = rng.integers(0, vocab, size=seq, dtype=np.int32)
    return out


# ---------------------------------------------------------------------------
# Plain float32 blocks, for an architecture's reference to use or not
# ---------------------------------------------------------------------------
def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta):
    """x [S, H, D]; positions 0..S-1; half-split rotation."""
    s, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def blocks(n: int, want: int) -> int:
    """Largest block <= want that divides n."""
    b = min(n, want)
    while n % b:
        b -= 1
    return b


def attention(q, k, v):
    """Causal grouped-query attention. q [S, H, D], k/v [S, Hk, D] -> [S, H,
    D]. One kv head and one block of queries at a time."""
    s, h, d = q.shape
    hk = k.shape[1]
    g = h // hk
    bq = blocks(s, ATTN_Q_BLOCK)
    nq = s // bq
    qg = q.reshape(s, hk, g, d)
    scale = d ** -0.5
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def one(idx):
        head, blk = idx // nq, idx % nq
        qb = jax.lax.dynamic_slice(qg, (blk * bq, head, 0, 0),
                                   (bq, 1, g, d))[:, 0]        # [bq, g, d]
        kh = jax.lax.dynamic_slice(k, (0, head, 0), (s, 1, d))[:, 0]
        vh = jax.lax.dynamic_slice(v, (0, head, 0), (s, 1, d))[:, 0]
        scores = jnp.einsum("qgd,kd->gqk", qb, kh) * scale
        q_pos = blk * bq + jnp.arange(bq)
        mask = key_pos[None, :] <= q_pos[:, None]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("gqk,kd->qgd", p, vh)                # [bq, g, d]

    out = jax.lax.map(one, jnp.arange(hk * nq))      # [hk*nq, bq, g, d]
    out = out.reshape(hk, nq, bq, g, d).transpose(1, 2, 0, 3, 4)
    return out.reshape(s, h, d)


def by_position_blocks(fn, x):
    """``fn`` over blocks of positions of x [S, ...], recomputed backward."""
    s = x.shape[0]
    b = blocks(s, POS_BLOCK)
    out = jax.lax.map(jax.checkpoint(fn), x.reshape(s // b, b, *x.shape[1:]))
    return out.reshape(s, *out.shape[2:])


def next_token_nll_sum(x, head, tokens):
    """Sum over positions of the next-token negative log likelihood of one
    row of ids [S], from its final hidden states x [S, D] and the head
    [D, V], a block of positions at a time."""
    # Position i predicts token i+1; the last position predicts nothing.
    targets = jnp.concatenate([tokens[1:], tokens[:1]])
    weight = (jnp.arange(tokens.shape[0]) < tokens.shape[0] - 1).astype(
        jnp.float32)

    def nll(args):
        xb, tb, wb = args
        logits = xb @ head
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - picked) * wb)[None]

    s = x.shape[0]
    b = blocks(s, POS_BLOCK)
    sums = jax.lax.map(jax.checkpoint(nll), (
        x.reshape(s // b, b, -1), targets.reshape(s // b, b),
        weight.reshape(s // b, b)))
    return jnp.sum(sums)


def mean_over_rows(row_nll_sum, tokens: jax.Array) -> jax.Array:
    """Mean next-token cross entropy of a batch of ids [B, S], a row at a
    time: ``row_nll_sum(row)`` is one row's sum over its positions."""
    b, s = tokens.shape
    sums = jax.lax.map(jax.checkpoint(row_nll_sum), tokens)
    return jnp.sum(sums) / (b * (s - 1))


# ---------------------------------------------------------------------------
# AdamW (optax.adamw: scale_by_adam, add_decayed_weights, scale by -lr)
# ---------------------------------------------------------------------------
def adamw_update(opt: dict, count: int, p, g, mu, nu):
    b1, b2 = opt["b1"], opt["b2"]
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * jnp.square(g)
    mhat = mu / (1 - b1 ** count)
    nhat = nu / (1 - b2 ** count)
    step = mhat / (jnp.sqrt(nhat) + opt["eps"]) + opt["weight_decay"] * p
    return p - opt["learning_rate"] * step, mu, nu


GRAD_SAMPLE = 4096      # entries of each leaf's first gradient compared


def sample_entries(seed: int, leaves: list) -> list:
    """The same ``GRAD_SAMPLE`` entries of every leaf, drawn from the seed
    and the leaf's index: a first-order reading of the gradient that costs a
    few hundred kilobytes to keep while the other side runs."""
    idx = [np.random.default_rng([int(seed), i]).integers(
        0, x.size, GRAD_SAMPLE).astype(np.int32)
        for i, x in enumerate(leaves)]
    f = jax.jit(lambda xs, js: [x.reshape(-1)[j].astype(jnp.float32)
                                for x, j in zip(xs, js)])
    return [np.asarray(x, np.float64).tolist() for x in f(leaves, idx)]


def leaf_norms(leaves: list) -> np.ndarray:
    f = jax.jit(lambda xs: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs]))
    return np.asarray(f(leaves), np.float64)


def change_norms(model, cfg: dict, seed: int, leaves: list) -> np.ndarray:
    """Per-leaf norm of (leaf - the seed's initial leaf), the initial leaf
    made again from the seed so that no second copy of the weights is kept."""
    def one(key, i, x):
        return jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - make_leaf(model, cfg, key, i))))
    f = jax.jit(lambda key, xs: jnp.stack(
        [one(key, i, x) for i, x in enumerate(xs)]))
    return np.asarray(f(seed_key(seed), leaves), np.float64)


def follow(model, cfg: dict, opt: dict, seed: int, batch: int, seq: int,
           steps: int, half: bool = False, offload_moments: bool = False,
           devices=None) -> dict:
    """The reference's own first ``steps`` steps from the seed, ``model``
    being the architecture's reference (``leaf_specs``, ``loss_fn``). Returns
    the readings the program is held to: each step's loss, the per-leaf norm
    of the first gradient, the per-leaf norm of the parameters' change.

    ``half`` is the planted fault: the reference put in the program's place
    on half of the batch, the mean taken over that half (the first half of
    the rows; of one row, its first half).
    ``offload_moments`` keeps Adam's moments on the host while a gradient is
    taken, where they do not fit beside its temporaries. Over several
    ``devices`` every matrix is split along its longer side and the compiler
    places the rest: the same plain program, held by more than one chip.
    """
    def value_and_grad(params, tokens):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda p: model.loss_fn(cfg, p, tokens))(params)

    shardings = None
    if devices is not None and len(devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(np.array(devices), ("x",))
        shardings = make_tree(model, cfg, lambda i, shape: NamedSharding(
            mesh, PartitionSpec(*(
                "x" if len(shape) > 1 and j == int(np.argmax(shape)) else None
                for j in range(len(shape))))))
    vg = jax.jit(value_and_grad, out_shardings=(None, shardings))
    upd = jax.jit(lambda c, p, g, m, n: jax.tree.map(
        lambda *a: adamw_update(opt, c, *a), p, g, m, n),
        static_argnums=0, donate_argnums=(1, 2, 3, 4))
    params = jax.jit(lambda key: make_params(model, cfg, key),
                     out_shardings=shardings)(seed_key(seed))
    mu = nu = None
    losses, grad_norms = [], None
    for step in range(steps):
        ids = token_rows(seed, step, batch, seq, cfg["vocab_size"])
        if half:
            ids = ids[:batch // 2] if batch > 1 else ids[:, :seq // 2]
        loss, grads = vg(params, jnp.asarray(ids))
        losses.append(float(loss))
        if step == 0:
            grad_norms = leaf_norms(flat(grads))
            grad_sample = sample_entries(seed, flat(grads))
            mu = jax.tree.map(jnp.zeros_like, grads)
            nu = jax.tree.map(jnp.zeros_like, grads)
        elif offload_moments:
            mu, nu = jax.device_put((mu, nu), (shardings, shardings)) \
                if shardings else jax.device_put((mu, nu))
        out = upd(step + 1, params, grads, mu, nu)
        is_leaf = lambda t: isinstance(t, tuple)      # noqa: E731
        params = jax.tree.map(lambda t: t[0], out, is_leaf=is_leaf)
        mu = jax.tree.map(lambda t: t[1], out, is_leaf=is_leaf)
        nu = jax.tree.map(lambda t: t[2], out, is_leaf=is_leaf)
        del out, grads
        if offload_moments and step + 1 < steps:
            host = jax.device_get((mu, nu))
            jax.tree.map(lambda x: x.delete(), (mu, nu))
            mu, nu = host
    change = change_norms(model, cfg, seed, flat(params))
    jax.tree.map(lambda x: x.delete() if hasattr(x, "delete") else None,
                 (params, mu, nu))
    return {"losses": losses, "grad_norms": grad_norms.tolist(),
            "grad_sample": grad_sample, "change_norms": change.tolist()}


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------
def worst_leaf_gap(got: list, want: list, keep=None) -> float:
    """Largest gap between the program's norm of a leaf and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    want_a, got_a = np.asarray(want, float), np.asarray(got, float)
    idx = [i for i in range(len(want_a)) if keep is None or keep[i]]
    med = statistics.median(want_a[idx])
    return float(max(abs(got_a[i] - want_a[i]) / max(want_a[i], med)
                     for i in idx))


def worst_sample_diff(got: list, want: list) -> float:
    """Largest norm of (the program's sampled entries of a leaf's first
    gradient - the reference's), against the reference's norm of that sample
    or of the median leaf's, whichever is larger. Unlike a gap of norms this
    is first order in rounding, so it is what a lower precision moves."""
    got_a, want_a = np.asarray(got, float), np.asarray(want, float)
    norms = np.linalg.norm(want_a, axis=1)
    floor = statistics.median(norms)
    diffs = np.linalg.norm(got_a - want_a, axis=1)
    return float(max(d / max(n, floor) for d, n in zip(diffs, norms)))


def compare(program: dict, ref: dict, limits: dict) -> dict:
    """Every number compared, beside its limit. ``program`` and ``ref`` hold
    ``losses``, ``grad_norms``, ``grad_sample``, ``change_norms``; ``program``
    also the count
    of fed ids that differ from the seed's (``feed_mismatch``). A limit that
    is absent or null means the number is reported and not held."""
    grad_med = statistics.median(ref["grad_norms"])
    alive = [g >= DEAD_LEAF_SHARE * grad_med for g in ref["grad_norms"]]
    numbers = {"feed_mismatch": float(program.get("feed_mismatch", 0))}
    for i, (got, want) in enumerate(zip(program["losses"], ref["losses"])):
        gap = abs(got - want) / abs(want)
        numbers[f"loss_gap_{i + 1}"] = gap if math.isfinite(gap) else 1e9
    numbers["grad_norm_gap"] = worst_leaf_gap(program["grad_norms"],
                                              ref["grad_norms"])
    numbers["change_norm_gap"] = worst_leaf_gap(program["change_norms"],
                                                ref["change_norms"], alive)
    numbers["grad_sample_diff"] = worst_sample_diff(
        program["grad_sample"], ref["grad_sample"])
    checks = {}
    for name, value in numbers.items():
        limit = limits.get(name)
        if not math.isfinite(value):
            value = 1e9
        checks[name] = {"value": value, "limit": limit}
    ok = all(c["limit"] is None or c["value"] <= c["limit"]
             for c in checks.values())
    held = [n for n, c in checks.items() if c["limit"] is not None]
    return {"correct": bool(ok and held), "checks": checks}
