"""Plain reference of a dense pre-norm decoder, its AdamW inner step and the
NoLoCo outer step, in straightforward ``jax.numpy`` at float32.

It follows the model as this repository's configuration states it (the
published departures are listed in each configuration file and in PERF.md):
LayerNorm (eps 1e-6) with scale and bias, rotary embedding over the whole
head (theta from the configuration, halves rotated), causal grouped-query
attention scaled by 1/sqrt(head_dim), a SwiGLU or squared-ReLU MLP, a final
LayerNorm and an untied output head; next-token cross-entropy averaged over
all tokens.  It imports nothing of the program: parameters are plain dicts
keyed by name and made by :mod:`bench.core.weights` from the seed.

``precision="fp8"`` is the control: every matrix product rounds both inputs
to float8 e4m3 with a per-tensor scale (and their gradients to e5m2, also
scaled), accumulating in float32.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

from bench.core import weights as W

F32 = jnp.float32
EPS = 1e-6
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


FP8_GRAD = jnp.float8_e5m2
FP8_GRAD_MAX = 57344.0


def _scaled_round(x, dtype, top):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = top / amax
    return (x * s).astype(dtype).astype(F32) / s


@jax.custom_vjp
def _q8(x):
    """Float8 rounding with a per-tensor scale, as fp8 training does it:
    e4m3 on the way forward, e5m2 (also scaled) for the gradient."""
    return _scaled_round(x, FP8, FP8_MAX)


def _q8_fwd(x):
    return _q8(x), None


def _q8_bwd(_, g):
    return (_scaled_round(g, FP8_GRAD, FP8_GRAD_MAX),)


_q8.defvjp(_q8_fwd, _q8_bwd)


def mm(spec: str, a, b, precision: str):
    if precision == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def layernorm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + EPS) * scale + bias


def rope(x, positions, theta: float):
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions[:, None, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def param_shapes(dims: dict) -> dict:
    """Name -> shape of every parameter (layered leaves lead with the layer
    axis), with the dtype the configuration serves them in."""
    d, h, kv, hd = dims["d_model"], dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
    f, v, n = dims["d_ff"], dims["vocab_size"], dims["num_layers"]
    s = {
        "embed/table": (v, d),
        "embed/unembed": (d, v),
        "final_norm/scale": (d,),
        "final_norm/bias": (d,),
    }
    blk = {
        "ln1/scale": (d,), "ln1/bias": (d,),
        "attn/w_q": (d, h, hd), "attn/w_k": (d, kv, hd),
        "attn/w_v": (d, kv, hd), "attn/w_o": (h, hd, d),
        "ln2/scale": (d,), "ln2/bias": (d,),
        "mlp/w_in": (d, f), "mlp/w_out": (f, d),
    }
    if dims["mlp"] == "swiglu":
        blk["mlp/w_gate"] = (d, f)
    for k, shp in blk.items():
        s["stack/scan/0/" + k] = (n,) + shp
    return s


def leaf_dtype(name: str, dims: dict):
    last = name.rsplit("/", 1)[-1]
    return F32 if last in ("scale", "bias") else jnp.dtype(dims["dtype"])


def make_params(words, dims: dict) -> dict:
    """All parameters as float32 (the served values, upcast)."""
    return {
        k: W.leaf_value(words, k, shp, leaf_dtype(k, dims), dims).astype(F32)
        for k, shp in param_shapes(dims).items()
    }


def make_layer(words, dims: dict, layer: int) -> dict:
    """One layer's parameters, float32, keyed without the stack prefix."""
    out = {}
    for k, shp in param_shapes(dims).items():
        if W.is_layered(k):
            short = k[len("stack/scan/0/"):]
            out[short] = W.layer_value(words, k, shp[1:], leaf_dtype(k, dims), dims, layer).astype(F32)
    return out


def attention(q, k, v, q_pos, precision: str, block: int = 512):
    """Causal GQA attention of queries at ``q_pos`` over keys 0..Sk-1, in
    query blocks so that the score matrix stays small."""
    h, kvh = q.shape[1], k.shape[1]
    g = h // kvh
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    scale = 1.0 / math.sqrt(q.shape[-1])
    kpos = jnp.arange(k.shape[0])
    outs = []
    for s0 in range(0, q.shape[0], block):
        qb = q[s0:s0 + block]
        sc = mm("qhd,khd->hqk", qb, k, precision) * scale
        mask = kpos[None, :] <= q_pos[s0:s0 + block][:, None]
        sc = jnp.where(mask[None], sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(mm("hqk,khd->qhd", p, v, precision))
    return jnp.concatenate(outs, axis=0)


def block_fwd(lp: dict, x, positions, dims: dict, precision: str):
    h = layernorm(x, lp["ln1/scale"], lp["ln1/bias"])
    q = rope(mm("sd,dhk->shk", h, lp["attn/w_q"], precision), positions, dims["rope_theta"])
    k = rope(mm("sd,dhk->shk", h, lp["attn/w_k"], precision), positions, dims["rope_theta"])
    v = mm("sd,dhk->shk", h, lp["attn/w_v"], precision)
    a = attention(q, k, v, positions, precision)
    x = x + mm("shk,hkd->sd", a, lp["attn/w_o"], precision)
    h = layernorm(x, lp["ln2/scale"], lp["ln2/bias"])
    u = mm("sd,df->sf", h, lp["mlp/w_in"], precision)
    if dims["mlp"] == "swiglu":
        u = jax.nn.silu(mm("sd,df->sf", h, lp["mlp/w_gate"], precision)) * u
    elif dims["mlp"] == "relu2":
        u = jnp.square(jax.nn.relu(u))
    else:
        raise ValueError(dims["mlp"])
    return x + mm("sf,fd->sd", u, lp["mlp/w_out"], precision)


def _layer(params: dict, l: int) -> dict:
    p = "stack/scan/0/"
    return {k[len(p):]: v[l] for k, v in params.items() if k.startswith(p)}


def row_loss(params: dict, tokens, labels, dims: dict, precision: str, chunk: int = 512):
    """Mean next-token NLL of one sequence."""
    positions = jnp.arange(tokens.shape[0])
    x = params["embed/table"][tokens]
    for l in range(dims["num_layers"]):
        x = jax.checkpoint(
            lambda lp, x: block_fwd(lp, x, positions, dims, precision)
        )(_layer(params, l), x)
    x = layernorm(x, params["final_norm/scale"], params["final_norm/bias"])

    def chunk_nll(xc, lc, w):
        logits = mm("sd,dv->sv", xc, w, precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        hit = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - hit)

    total = 0.0
    for s0 in range(0, tokens.shape[0], chunk):
        total = total + jax.checkpoint(chunk_nll)(
            x[s0:s0 + chunk], labels[s0:s0 + chunk], params["embed/unembed"]
        )
    return total / tokens.shape[0]


def batch_loss(params: dict, tokens, labels, dims: dict, precision: str):
    """Mean over rows of the row means (rows have equal lengths)."""
    losses = jax.lax.map(
        lambda tl: row_loss(params, tl[0], tl[1], dims, precision), (tokens, labels)
    )
    return jnp.mean(losses)


def global_norm(tree: dict):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in tree.values()))


def adamw_step(params, mu, nu, count, grads, opt: dict):
    """Clip by global norm, then AdamW with bias correction (decoupled decay)."""
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gn, 1e-12))
    grads = {k: g * scale for k, g in grads.items()}
    count = count + 1
    c1 = 1.0 - opt["b1"] ** count
    c2 = 1.0 - opt["b2"] ** count
    mu = {k: opt["b1"] * mu[k] + (1 - opt["b1"]) * grads[k] for k in grads}
    nu = {k: opt["b2"] * nu[k] + (1 - opt["b2"]) * grads[k] ** 2 for k in grads}
    new = {
        k: params[k] - opt["lr"] * ((mu[k] / c1) / (jnp.sqrt(nu[k] / c2) + opt["eps"])
                                    + opt["weight_decay"] * params[k])
        for k in params
    }
    return new, mu, nu, count, grads


def noloco_outer(phi, delta_mom, theta, partner_delta, partner_phi, outer: dict):
    """Eqs. 1-3 of the NoLoCo paper for a group of two, with the +beta sign
    of its Appendix A: delta' = a*delta + b*mean(Delta) - g*(phi - mean(phi)),
    phi' = phi + delta', and the fast weights restart from phi'."""
    a, b, g = outer["alpha"], outer["beta"], outer["gamma"]
    new_phi, new_d = {}, {}
    for k in phi:
        dk = theta[k] - phi[k]
        mean_d = 0.5 * (dk + partner_delta[k])
        mean_phi = 0.5 * (phi[k] + partner_phi[k])
        new_d[k] = a * delta_mom[k] + b * mean_d - g * (phi[k] - mean_phi)
        new_phi[k] = phi[k] + new_d[k]
    return new_phi, new_d


def partner_table(step: int, world: int, seed: int):
    """Random perfect matching of outer step ``step``: a permutation drawn
    from (seed, step), paired off in consecutive twos; an odd one out pairs
    with itself."""
    import numpy as np

    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    perm = np.asarray(jax.random.permutation(key, world))
    partner = np.arange(world)
    for i in range(0, (world // 2) * 2, 2):
        a, b = int(perm[i]), int(perm[i + 1])
        partner[a], partner[b] = b, a
    return partner


def _bucket(n: int) -> int:
    for b in (512, 1024, 2048, 4096, 8192, 16384):
        if n <= b:
            return b
    return -(-n // 4096) * 4096


def forward_logits(words, dims: dict, seqs: list, picks: list, precision: str, pick_cap: int):
    """Layer by layer over several sequences: for sequence ``i`` the float32
    logits at positions ``picks[i]`` (padded to ``pick_cap`` rows; the extra
    rows repeat the last pick).  Only one layer's weights live at a time, so a
    model larger than the chip's memory in float32 fits; sequences are padded
    at the end to a few fixed lengths (causal attention leaves earlier
    positions untouched), so the compiled programs repeat from run to run."""
    dt = jnp.dtype(dims["dtype"])
    v, d = dims["vocab_size"], dims["d_model"]
    emb = jax.jit(lambda w, t: W.leaf_value(w, "embed/table", (v, d), dt, dims).astype(F32)[t])
    layer_fn = jax.jit(lambda w, l: make_layer(w, dims, l))
    blk = jax.jit(lambda lp, x: block_fwd(lp, x, jnp.arange(x.shape[0]), dims, precision))

    def head_impl(w, x, p):
        sc = W.leaf_value(w, "final_norm/scale", (d,), F32, dims)
        b = W.leaf_value(w, "final_norm/bias", (d,), F32, dims)
        un = W.leaf_value(w, "embed/unembed", (d, v), dt, dims).astype(F32)
        return mm("sd,dv->sv", layernorm(x[p], sc, b), un, precision)

    head = jax.jit(head_impl)
    xs = []
    for s in seqs:
        t = np.zeros((_bucket(len(s)),), np.int32)
        t[: len(s)] = s
        xs.append(emb(words, jnp.asarray(t)))
    for l in range(dims["num_layers"]):
        lp = layer_fn(words, jnp.int32(l))
        xs = [blk(lp, x) for x in xs]
        del lp
    out = []
    for x, p in zip(xs, picks):
        pp = np.full((pick_cap,), p[-1], np.int32)
        pp[: len(p)] = p
        out.append(head(words, x, jnp.asarray(pp)))
    return out
