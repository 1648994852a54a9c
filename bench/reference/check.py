"""The comparisons that decide ``correct``, each against the plain
reference ``ref`` that the cell's configuration names (a module under
``bench/reference/``, reached through the cell).

Serving: for a sample of the requests the window finished, the reference
runs once over each prompt with its served tokens, and the number is the
widest gap by which a served token's reference logit lies below what the
sampling rule allows: the reference's best logit for greedy requests,
the smallest logit the reference's own top-k / top-p filter keeps for
sampled ones.  The control is the fp8 reference in the program's place:
at the same positions it picks its token by the same rule from its own
logits (argmax, or a seeded draw from its filtered set), and its gap is
read the same way.

Training: each of the first steps' losses, the first gradient as the
optimizer gets it (read from the momentum after one step), and the
parameters' change after the steps, each per leaf against the reference
running the configuration's SGD-momentum update in f32.  On several
chips the reference's rows are split evenly over them and the means
averaged, so it takes as long as on one chip with its share of the rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

SEQ_BUCKET = 1024       # reference sequences padded to a multiple of this


# ---------------------------------------------------------------------------
# serving


def _kept_floor(x, temperature, top_k, top_p):
    """Smallest logit the top-k then top-p filter keeps, per row.
    x: (R, V) logits.  Greedy (temperature 0) keeps only the best."""
    if not temperature:
        return jnp.max(x, -1)
    top = jax.lax.top_k(x / temperature, top_k)[0]          # descending
    pr = jnp.exp(top - top[:, :1])
    above = jnp.cumsum(pr, -1) - pr
    kept = above < top_p * jnp.sum(pr, -1, keepdims=True)
    return jnp.min(jnp.where(kept, top, jnp.inf), -1) * temperature


def _control_pick(x, u, temperature, top_k, top_p):
    """The token the control emits at each row: argmax, or a draw with
    uniforms ``u`` from its filtered distribution."""
    if not temperature:
        return jnp.argmax(x, -1)
    top, idx = jax.lax.top_k(x / temperature, top_k)
    pr = jnp.exp(top - top[:, :1])
    above = jnp.cumsum(pr, -1) - pr
    pr = jnp.where(above < top_p * jnp.sum(pr, -1, keepdims=True), pr, 0.0)
    c = jnp.cumsum(pr, -1)
    j = jnp.argmax(c > u[:, None] * c[:, -1:], -1)
    return jnp.take_along_axis(idx, j[:, None], -1)[:, 0]


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


def serve_gaps(ref, params, samples, d, sampling, *, max_out: int,
               seed: int, control: bool = False):
    """Gaps of every served token of ``samples`` (a list of (prompt,
    served tokens)): the widest (``logit_gap``) and the mean over tokens
    (``mean_logit_gap``), and with ``control`` the fp8 control's.
    Returns {"program": {...}, "control": {...} | None, "tokens": int}."""
    T = float(sampling.get("temperature", 0.0))
    k, p = sampling.get("top_k"), sampling.get("top_p")
    k = int(k or d["V"])
    p = float(p if p is not None else 1.0)

    def run(quant):
        return jax.jit(lambda pr, t, r: ref.logits_at(pr, t, r, d, quant))

    ref_f, ctl_f = run(None), (run("fp8") if control else None)
    rng = np.random.default_rng(seed)
    prog, ctl = [], []
    for prompt, out in samples:
        seq = list(prompt) + list(out[:-1])
        S = _pad_to(len(seq), SEQ_BUCKET)
        toks = np.zeros(S, np.int32)
        toks[:len(seq)] = seq
        n = len(out)
        rows = np.full(max_out, len(prompt) - 1 + n - 1, np.int32)
        rows[:n] = len(prompt) - 1 + np.arange(n)
        served = jnp.asarray(np.asarray(out, np.int32))
        want = ref_f(params, jnp.asarray(toks), jnp.asarray(rows))[:n]
        floor = _kept_floor(want, T, k, p)
        got = jnp.take_along_axis(want, served[:, None], -1)[:, 0]
        prog.append(np.asarray(jnp.maximum(floor - got, 0.0)))
        if control:
            c = ctl_f(params, jnp.asarray(toks), jnp.asarray(rows))[:n]
            u = jnp.asarray(rng.random(n), jnp.float32)
            pick = _control_pick(c, u, T, k, p)
            cg = jnp.take_along_axis(want, pick[:, None], -1)[:, 0]
            ctl.append(np.asarray(jnp.maximum(floor - cg, 0.0)))
            del c
        del want

    def stats(gaps):
        g = np.concatenate(gaps) if gaps else np.zeros(0)
        return {"logit_gap": float(g.max(initial=0.0)),
                "mean_logit_gap": float(g.mean()) if g.size else 0.0}

    return {"program": stats(prog), "control": stats(ctl) if control else None,
            "tokens": int(sum(len(o) for _, o in samples))}


# ---------------------------------------------------------------------------
# training


def lr_scale(step: int, tr: dict) -> float:
    """The configuration's schedule: linear warm-up, then cosine down to
    ``final_frac`` of the rate over ``total_steps``."""
    w, total, ff = tr["warmup_steps"], tr["total_steps"], tr["final_frac"]
    if step < w:
        return step / max(w, 1)
    t = min(max((step - w) / max(total - w, 1), 0.0), 1.0)
    return ff + (1 - ff) * 0.5 * (1 + np.cos(np.pi * t))


def _loss_and_grad(ref, d, quant, mesh):
    """The reference's mean loss over a batch and its gradient, from the
    stored parameters upcast to f32; over a mesh of several chips each
    takes an equal block of the rows and the means are averaged."""
    def lg(p, t):
        return ref.loss_and_grad(
            jax.tree.map(lambda a: a.astype(jnp.float32), p), t, d, quant)

    if mesh is None:
        return jax.jit(lg)
    from jax.sharding import PartitionSpec as P
    n = mesh.size

    def rows(p, t):
        loss, g = lg(p, t)
        return (jax.lax.psum(loss, "rows") / n,
                jax.tree.map(lambda x: jax.lax.psum(x, "rows") / n, g))

    # the reference's own scans carry unvarying zeros: no replication check
    return jax.jit(jax.shard_map(rows, mesh=mesh, in_specs=(P(), P("rows")),
                                 out_specs=(P(), P()), check_vma=False))


def reference_steps(ref, params, batches, d, tr: dict, quant=None,
                    devices=None):
    """Run the configuration's SGD-momentum steps from ``params``:
    gradients and update in f32, momentum kept in f32, parameters stored
    between steps in their configured dtype (bf16), as the configuration
    states.  With several ``devices`` the rows of each batch are split
    over them.  Returns (losses, first-step gradient norms per leaf,
    parameter-change norms per leaf after the steps)."""
    lr, mu, wd = tr["lr"], tr["momentum"], tr["weight_decay"]
    mesh = None
    if devices is not None and len(devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        mesh = Mesh(np.asarray(devices), ("rows",))
        params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
    lg = _loss_and_grad(ref, d, quant, mesh)

    @jax.jit
    def update(p, m, g, scale):
        g = jax.tree.map(lambda g, p: g + wd * p.astype(jnp.float32), g, p)
        m = jax.tree.map(lambda m, g: mu * m + g, m, g)
        p = jax.tree.map(lambda p, m: (p.astype(jnp.float32) - lr * scale * m)
                         .astype(p.dtype), p, m)
        return p, m

    p0 = params
    p = p0
    m = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), p0)
    losses, g_norms = [], None
    for i, toks in enumerate(batches):
        loss, g = lg(p, jnp.asarray(toks))
        losses.append(float(loss))
        if i == 0:
            g_norms = leaf_norms(g)
        p, m = update(p, m, g, lr_scale(i, tr))
        del g
    change = leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0))
    return losses, g_norms, change


def leaf_norms(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): float(jnp.linalg.norm(
        v.astype(jnp.float32).ravel())) for k, v in flat}


def worst_leaf_gap(prog: dict, ref: dict, moving: set) -> tuple[float, str]:
    """Largest |norm_prog - norm_ref| over the leaves in ``moving``,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    med = float(np.median([ref[k] for k in moving]))
    worst, at = 0.0, ""
    for k in sorted(moving):
        g = abs(prog[k] - ref[k]) / max(ref[k], med)
        if g > worst:
            worst, at = g, k
    return worst, at


def moving_leaves(ref_grad: dict, frac: float = 1e-3) -> set:
    """Leaves the reference moves: gradient norm at least ``frac`` of the
    median leaf's.  A leaf below it (a key bias under softmax) moves by
    round-off alone and is left out, by this rule and not by name."""
    med = float(np.median(list(ref_grad.values())))
    return {k for k, v in ref_grad.items() if v >= frac * med}


def train_gaps(prog, ref, moving):
    """((loss gap, ""), (grad gap, worst leaf), (change gap, worst leaf))
    of a run's (losses, first-gradient norms, change norms) against the
    reference's: the loss as the largest relative gap over the steps."""
    lp, gp, cp = prog
    lr_, gr, cr = ref
    loss = max(abs(a - b) / abs(b) for a, b in zip(lp, lr_))
    return ((loss, ""), worst_leaf_gap(gp, gr, moving),
            worst_leaf_gap(cp, cr, moving))
