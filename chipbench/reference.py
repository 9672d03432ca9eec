"""Plain float32 reference of the compressed train step.

It imports nothing of the program. From the configuration file it takes the
sizes, and it lays the parameters out under the same leaf paths as the
program's tree (the data convention the weights are made in), so both
sides start from the same weights made from the seed. One step:

1. loss and gradient of the language model in float32, matmuls at
   ``highest`` precision: the layers and the final norm as the
   configuration's family file (``families/<family>.py``) gives them, tied
   unembedding, token-mean cross entropy over all but the last position of
   each row, plus the family's router loss where its layers route;
2. error feedback: the target is gradient plus carried residual;
3. gspar (the paper's Algorithm 3, greedy, two rescales) per row — one row
   per layer of a stacked leaf, else the whole leaf; leaves under
   ``min_leaf_size`` go dense — then a Bernoulli draw from the reference's
   own key and ``Q = z * x / p``; the new residual is ``x - Q``;
4. one worker (every cell runs on one chip), so the synced gradient is
   ``Q`` itself;
5. Adam with float32 moments.

Parameters and residual are stored in the configuration's dtype (bfloat16)
between steps, as the configuration states they are kept.

``lowp=True`` is the control: the same step with every matmul operand
rounded to float8 (e4m3), the precision below the configuration's
bfloat16. ``fault`` plants one of the faults the comparison must catch:
``"half_batch"`` (the loss mean over the first half of each row only) and
``"answer"`` (the synced gradient of the largest leaf doubled)."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench import families, weights

HI = jax.lax.Precision.HIGHEST
BETA1, BETA2, ADAM_EPS = 0.9, 0.999, 1e-8


# ---------------------------------------------------------------------------
# layout
# ---------------------------------------------------------------------------

def blocks(conf: dict) -> list:
    """``[(prefix, layers, kind)]`` in program order: each prelude block,
    unstacked (``layers`` None) under ``prelude/p<j>_<kind>/``, then each
    kind of the period, stacked ``layers`` deep under ``blocks/b<j>_<kind>/``,
    as many periods as the layers left after the prelude fill."""
    fam = families.get(conf["family"])
    prelude = getattr(fam, "prelude", lambda conf: ())(conf)
    periods, rest = divmod(conf["num_hidden_layers"] - len(prelude),
                           len(fam.PERIOD))
    if rest:
        raise ValueError(f"{conf['family']}: {conf['num_hidden_layers']} "
                         f"layers are no whole number of periods")
    out = [(f"prelude/p{j}_{kind}/", None, kind)
           for j, kind in enumerate(prelude)]
    out += [(f"blocks/b{j}_{kind}/", periods, kind)
            for j, kind in enumerate(fam.PERIOD)]
    return out


def final_norm(conf: dict) -> dict:
    """``{leaf under final_ln/: shape}``: the family's, else a LayerNorm's
    scale and bias."""
    fam = families.get(conf["family"])
    if hasattr(fam, "final_norm"):
        return fam.final_norm(conf)
    return {"bias": (conf["hidden_size"],), "scale": (conf["hidden_size"],)}


def layout(conf: dict) -> dict:
    """``{leaf path: shape}`` of the model described by ``conf``."""
    fam = families.get(conf["family"])
    out = {"embed/table": (conf["vocab_size"], conf["hidden_size"])}
    out.update({"final_ln/" + k: s for k, s in final_norm(conf).items()})
    for prefix, layers, kind in blocks(conf):
        lead = () if layers is None else (layers,)
        out.update({prefix + k: lead + s
                    for k, s in fam.block(conf, kind).items()})
    return dict(sorted(out.items()))


def block_leaf(path: str) -> str | None:
    """A leaf's path inside its block (``attn/wq``), None outside blocks."""
    head, _, rest = path.partition("/")
    if head in ("prelude", "blocks"):
        return rest.partition("/")[2]
    return None


def rows_of(path: str, shape: tuple) -> int:
    """Rows gspar compresses a leaf in: one per layer of a stacked leaf."""
    if path.startswith("blocks/") and len(shape) >= 2 and shape[0] > 1:
        return shape[0]
    return 1


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

def _op(lowp: bool):
    """The reference's matmul: float32 at highest precision, or for the
    control with both operands rounded to float8 e4m3 first. The rounding
    passes the cotangent straight through: a cast's own gradient would
    round the cotangent to float8 too, where gradients of 1e-5 vanish."""
    def q8(x):
        if not lowp:
            return x
        return x + jax.lax.stop_gradient(
            x.astype(jnp.float8_e4m3fn).astype(jnp.float32) - x)

    def mm(eq, a, b):
        return jnp.einsum(eq, q8(a), q8(b), precision=HI)
    return mm


def layernorm(x, scale, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def causal_attention(mm, q, k, v, window=None, block=512):
    """Causal attention of one row, in blocks of queries, over the last
    ``window`` keys where one is given. q [S, H, Dk] (already scaled),
    k [S, KV, Dk], v [S, KV, Dv]; head h reads kv head h // (H // KV)."""
    s, h, _ = q.shape
    groups = h // k.shape[1]
    kk = jnp.repeat(k, groups, axis=1)
    vv = jnp.repeat(v, groups, axis=1)
    blk = min(block, s)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 0)
        sc = mm("qhd,khd->hqk", qb, kk)
        qpos = i * blk + jnp.arange(blk)
        ok = kpos[None, :] <= qpos[:, None]
        if window is not None:
            ok = ok & (qpos[:, None] - kpos[None, :] < window)
        sc = jnp.where(ok[None], sc, -1e30)
        pr = jax.nn.softmax(sc, axis=-1)
        return mm("hqk,khd->qhd", pr, vv)

    out = jax.lax.map(one, jnp.arange(s // blk))
    return out.reshape(s, h, v.shape[-1])


def _row_nll(conf, mm, params, tokens, block=1024):
    """Summed next-token NLL of one row over positions ``[0, S-1)``, their
    count, and the router statistics of each layer that returns them."""
    fam = families.get(conf["family"])
    table = params["embed/table"]
    x = table[tokens]
    stats = []
    for prefix, layers, kind in blocks(conf):
        own = {k[len(prefix):]: v for k, v in params.items()
               if k.startswith(prefix)}
        f = jax.checkpoint(functools.partial(fam.layer, conf, mm, kind))
        for i in range(layers or 1):
            p = own if layers is None else {k: v[i] for k, v in own.items()}
            out = f(p, x)
            x, st = out if isinstance(out, tuple) else (out, None)
            if st is not None:
                stats.append(st)
    fin = {k[len("final_ln/"):]: v for k, v in params.items()
           if k.startswith("final_ln/")}
    if hasattr(fam, "final_norm_apply"):
        x = fam.final_norm_apply(conf, fin, x)
    else:
        x = layernorm(x, fin["scale"], fin["bias"], fam.final_norm_eps(conf))
    s = x.shape[0]
    targets = jnp.concatenate([tokens[1:], tokens[:1]])
    valid = (jnp.arange(s) < s - 1).astype(jnp.float32)
    blk = min(block, s)

    @jax.checkpoint
    def one(i):
        xb = jax.lax.dynamic_slice_in_dim(x, i * blk, blk, 0)
        tb = jax.lax.dynamic_slice_in_dim(targets, i * blk, blk, 0)
        vb = jax.lax.dynamic_slice_in_dim(valid, i * blk, blk, 0)
        logits = mm("sd,vd->sv", xb, table)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - gold) * vb)

    nll = jnp.sum(jax.lax.map(one, jnp.arange(s // blk)))
    return nll, jnp.sum(valid), stats


def loss_fn(conf: dict, params: dict, tokens, lowp=False, fault=None):
    """Token-mean next-token cross entropy over the batch [B, S], plus the
    family's router loss of the statistics its layers return, each with a
    leading batch axis (the program adds its router loss so)."""
    mm = _op(lowp)
    if fault == "half_batch":
        tokens = tokens[:, :tokens.shape[1] // 2]
    nll, n, stats = jax.vmap(
        lambda t: _row_nll(conf, mm, params, t))(tokens)
    loss = jnp.sum(nll) / jnp.sum(n)
    if stats:
        loss = loss + families.get(conf["family"]).router_loss(conf, stats)
    return loss


# ---------------------------------------------------------------------------
# compression, error feedback, Adam
# ---------------------------------------------------------------------------

def gspar_probabilities(x, rho, iters=2):
    """Algorithm 3 on one row: p0 = min(rho d |x| / |x|_1, 1), then
    ``iters`` rescales of the unsaturated set toward sum(p) = rho d."""
    a = jnp.abs(x)
    d = a.shape[0]
    rho_d = jnp.float32(rho * d)
    l1 = jnp.sum(a)
    p = jnp.where(l1 > 0, jnp.minimum(rho_d * a / jnp.where(l1 > 0, l1, 1.0),
                                      1.0), 0.0)
    for _ in range(iters):
        active = p < 1.0
        target = rho_d - (d - jnp.sum(active, dtype=jnp.float32))
        mass = jnp.sum(jnp.where(active, p, 0.0))
        c = jnp.where(mass > 0, target / jnp.where(mass > 0, mass, 1.0), 0.0)
        p = jnp.minimum(jnp.maximum(c, 1.0) * p, 1.0)
    return jnp.where(a > 0, p, 0.0)


def gspar(x, key, rho):
    """Q(x) = z x / p with z ~ Bernoulli(p), rows of x [rows, d]."""
    def row(xr, k):
        p = gspar_probabilities(xr, rho)
        z = jax.random.uniform(k, xr.shape, jnp.float32) < p
        return jnp.where(z, xr / jnp.where(p > 0, p, 1.0), 0.0)
    keys = jax.random.split(key, x.shape[0])
    return jax.lax.map(lambda a: row(*a), (x, keys))


def _leaf_norms(tree: dict):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                      for _, v in sorted(tree.items())])


def make_step(conf: dict, traffic: dict, lowp=False, fault=None):
    """``step(params, m, v, res, t, tokens, key) -> (params, m, v, res,
    loss, dense grad norms, state grad norms)`` of one worker, norms per
    leaf in sorted path order; t counts from 1. The state grad is
    ``m / (1 - beta1) + res`` after the step: at t = 1 the gradient the
    compressor was handed, as the state holds it."""
    comp = traffic["compression"]
    rho, min_leaf = comp["rho"], comp["min_leaf_size"]
    lr = traffic["lr"]
    store = jnp.dtype(conf["dtype"])
    shapes = layout(conf)
    paths = sorted(shapes)
    biggest = max(paths, key=lambda k: math.prod(shapes[k]))

    def step(params, m, v, res, t, tokens, key):
        p32 = {k: x.astype(jnp.float32) for k, x in params.items()}
        loss, g = jax.value_and_grad(
            lambda p: loss_fn(conf, p, tokens, lowp, fault))(p32)
        q, new_res = {}, {}
        for i, path in enumerate(paths):
            x = g[path] + res[path].astype(jnp.float32)
            if x.size < min_leaf:
                q[path] = x
            else:
                rows = rows_of(path, x.shape)
                q[path] = gspar(x.reshape(rows, -1),
                                jax.random.fold_in(key, i),
                                rho).reshape(x.shape)
            new_res[path] = (x - q[path]).astype(store)
        if fault == "answer":
            q[biggest] = 2.0 * q[biggest]
        tf = t.astype(jnp.float32)
        new_p, new_m, new_v = {}, {}, {}
        for path in paths:
            mk = BETA1 * m[path] + (1 - BETA1) * q[path]
            vk = BETA2 * v[path] + (1 - BETA2) * jnp.square(q[path])
            upd = (mk / (1 - BETA1 ** tf)) / (
                jnp.sqrt(vk / (1 - BETA2 ** tf)) + ADAM_EPS)
            new_p[path] = (p32[path] - lr * upd).astype(store)
            new_m[path], new_v[path] = mk, vk
        # the first gradient as the state after one step holds it: the
        # optimizer's share m / (1 - beta1) plus what error feedback kept
        first = {k: new_m[k] / (1 - BETA1) + new_res[k].astype(jnp.float32)
                 for k in paths}
        return (new_p, new_m, new_v, new_res, loss, _leaf_norms(g),
                _leaf_norms(first))
    return step


class Reference:
    """The reference's compiled programs for one configuration and traffic:
    build once, then ``run`` it on as many seeds as needed."""

    def __init__(self, conf: dict, traffic: dict, lowp=False, fault=None):
        shapes = layout(conf)
        store = jnp.dtype(conf["dtype"])
        self.paths = sorted(shapes)

        def init(wkey):
            params = weights.make(conf["init"], wkey, shapes, store)
            m = {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()}
            v = {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()}
            res = {k: jnp.zeros(s, store) for k, s in shapes.items()}
            return params, m, v, res

        def change(params, wkey):
            p0 = weights.make(conf["init"], wkey, shapes, store)
            return _leaf_norms({k: params[k].astype(jnp.float32)
                                - p0[k].astype(jnp.float32) for k in shapes})

        self.init = jax.jit(init)
        self.step = jax.jit(make_step(conf, traffic, lowp, fault),
                            donate_argnums=(0, 1, 2, 3))
        self.change = jax.jit(change)

    def run(self, wkey, rkey, batches: list) -> dict:
        """Steps from the weights of ``wkey`` on ``batches`` (one token array
        per step), drawing from ``rkey``. Returns the readings the
        comparison needs, as host numbers, per leaf in sorted path order."""
        params, m, v, res = self.init(wkey)
        losses = []
        for i, tokens in enumerate(batches):
            params, m, v, res, loss, gnorm, fnorm = self.step(
                params, m, v, res, jnp.int32(i + 1), tokens,
                jax.random.fold_in(rkey, i))
            losses.append(float(loss))
            if i == 0:
                dense_norms = [float(x) for x in gnorm]
                first_norms = [float(x) for x in fnorm]
        del m, v, res
        changes = [float(x) for x in self.change(params, wkey)]
        return {"paths": self.paths, "loss": losses,
                "dense_grad_norm": dense_norms,
                "first_grad_norm": first_norms, "change_norm": changes}
