"""Plain reference of qwen2-0.5b under HGQ, and the benchmark's weights.

Straightforward jax.numpy, written from the published descriptions and
imports nothing of the program under test:

* the Qwen2 decoder (arXiv:2407.10671): RMSNorm, GQA attention with
  q/k/v biases and rotate-half RoPE, a SwiGLU MLP, tied embeddings;
* HGQ (arXiv:2405.00645): every weight on a per-channel 2^-f grid and
  every activation on a per-tensor one, Algorithm 1's surrogate
  gradient in training, the ~EBOPs term of Eq. 5 from running ranges
  (Eq. 3) and the L1 term of Eq. 16;
* the serving format of the configuration's plan: plan-width mantissas
  with a per-channel power-of-two scale capped to fit the channel's
  largest weight, and a KV cache of ``kv_bits`` mantissas on a per-row
  (token x kv head) power-of-two grid;
* AdamW with global-norm clipping.

The attention probabilities are quantized before they are normalized
(the row's exp(s - max) goes on the 2^-f grid, the division comes
after); in training that holds for sequences of up to 1024 tokens, one
key block.

``dtype`` is the precision of the matmul operands (products accumulate
in float32; the residual stream and every elementwise step stay
float32).  float32 computes at ``highest``.  The configuration states
bfloat16 operands (the TPU's default precision for float32 matmuls),
so the control is float8 (e4m3) operands (``bench/control.py``,
``tests/test_control.py``): ``SCALED_FP8`` puts each operand, and in
the backward pass each output cotangent, on a per-tensor power-of-two
scale into e4m3's range first, as float8 training does; a plain e4m3
dtype casts them as they are.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List

import jax
import jax.numpy as jnp

LN2 = 0.6931471805599453
RANGE_DECAY = 0.999
sg = jax.lax.stop_gradient
F32 = jnp.float32


# --------------------------------------------------------------- sizes --

def sizes(cfg: Dict) -> Dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(L=cfg["num_hidden_layers"], d=d, H=H,
                KV=cfg["num_key_value_heads"], hd=d // H,
                ff=cfg["intermediate_size"], V=cfg["vocab_size"],
                theta=cfg["rope_theta"], eps=cfg["rms_norm_eps"])


def model(cfg: Dict) -> Dict:
    """The model as ``bench/work.py`` counts it: every projection and the
    tied head run the packed kernel; GQA attention."""
    z = sizes(cfg)
    d, H, KV, hd, ff = z["d"], z["H"], z["KV"], z["hd"], z["ff"]
    layer = {f"layers/{k}/kernel": kn for k, kn in (
        ("attn/wq", (d, H * hd)), ("attn/wk", (d, KV * hd)),
        ("attn/wv", (d, KV * hd)), ("attn/wo", (H * hd, d)),
        ("mlp/gate", (d, ff)), ("mlp/up", (d, ff)), ("mlp/down", (ff, d)))}
    head = ("embed/table", d, z["V"])
    return {"L": z["L"], "layer": layer, "head": head,
            "qmatmul": set(layer) | {head[0]}, "attention": (H, KV, hd),
            "wkv": None}


def program_config(cfg: Dict) -> Dict:
    """What the program's model config has to hold for this file."""
    z = sizes(cfg)
    return {"n_layers": z["L"], "d_model": z["d"], "n_heads": z["H"],
            "n_kv": z["KV"], "d_ff": z["ff"], "vocab": z["V"],
            "rope_theta": z["theta"]}


# ------------------------------------------------------------- weights --

def make_weights(key, cfg: Dict):
    """(params, qstate) in the layout the program's model takes, every
    leaf drawn from ``key``: LeCun-uniform kernels, uniform biases and
    embedding, norm gains about 1, fractional bits uniform in the
    configuration's ``hgq_init`` ranges, activation ranges at zero."""
    z = sizes(cfg)
    L, d, H, KV, hd, ff, V = (z[k] for k in
                              ("L", "d", "H", "KV", "hd", "ff", "V"))
    init = cfg["hgq_init"]
    keys = iter(jax.random.split(key, 96))

    def unif(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, F32, lo, hi)

    def fbits(shape, which):
        lo, hi = init[which]
        return unif(shape, lo, hi)

    def dense(din, dout, bias, out_q):
        lim = math.sqrt(3.0 / din)
        p = {"kernel": {"w": unif((L, din, dout), -lim, lim),
                        "f": fbits((L, 1, dout), "weight_f")}}
        q = {}
        if bias:
            b = init["bias_scale"]
            p["bias"] = {"w": unif((L, dout), -b, b),
                         "f": fbits((L, dout), "weight_f")}
        if out_q:
            p["out_f"] = fbits((L,), "act_f")
            q["out"] = _zero_range(L)
        return p, q

    def norm(lead):
        lo, hi = init["norm_scale"]
        shape = (lead, d) if lead else (d,)
        return ({"scale": unif(shape, lo, hi),
                 "out_f": fbits((lead,) if lead else (), "act_f")},
                {"out": _zero_range(lead)})

    p, q = {}, {}
    t = init["table_scale"]
    p["embed"] = {"table": {"w": unif((V, d), -t, t),
                            "f": fbits((1, d), "table_f")}}
    q["embed"] = {}
    lp, lq = {}, {}
    lp["ln1"], lq["ln1"] = norm(L)
    lp["ln2"], lq["ln2"] = norm(L)
    attn_p, attn_q = {}, {}
    for name, dout in (("wq", H * hd), ("wk", KV * hd), ("wv", KV * hd)):
        attn_p[name], attn_q[name] = dense(d, dout, True, True)
    attn_p["wo"], attn_q["wo"] = dense(H * hd, d, False, False)
    attn_p["probs_f"] = fbits((L,), "act_f")
    attn_p["attnout_f"] = fbits((L,), "act_f")
    attn_q["attnout"] = _zero_range(L)
    lp["attn"], lq["attn"] = attn_p, attn_q
    mlp_p, mlp_q = {}, {}
    mlp_p["gate"], mlp_q["gate"] = dense(d, ff, False, True)
    mlp_p["up"], mlp_q["up"] = dense(d, ff, False, True)
    mlp_p["down"], mlp_q["down"] = dense(ff, d, False, False)
    lp["mlp"], lq["mlp"] = mlp_p, mlp_q
    p["layers"], q["layers"] = lp, lq
    p["final_norm"], q["final_norm"] = norm(0)
    return p, q


def _zero_range(lead):
    """An activation's running (vmin, vmax), as a 2-tuple of zeros."""
    shape = (lead,) if lead else ()
    return (jnp.zeros(shape, F32), jnp.zeros(shape, F32))


# ---------------------------------------------------------- quantizers --

def exp2i(f):
    """2^f for integer-valued f, exactly (ldexp, not exp2)."""
    fi = jnp.clip(jnp.asarray(f, F32), -126.0, 127.0)
    return jnp.ldexp(F32(1.0), fi.astype(jnp.int32))


def q_eval(x, f):
    """Eq. 4: round(x * 2^f) / 2^f, f rounded to an integer."""
    s = exp2i(jnp.floor(f.astype(F32) + 0.5))
    return (jnp.floor(x.astype(F32) * s + 0.5) / s).astype(x.dtype)


def q_train(x, f):
    """Algorithm 1: straight-through in x; d/df = ln2 * (x - x_q)."""
    x32 = x.astype(F32)
    f32 = f.astype(F32)
    fi = f32 + sg(jnp.floor(f32 + 0.5) - f32)
    s = exp2i(sg(fi))
    xq = sg(jnp.floor(x32 * s + 0.5) / s)
    delta = sg(x32 - xq)
    delta = sg(delta + LN2 * fi * delta) - LN2 * fi * delta
    return (x32 - delta).astype(x.dtype)


def grad_scale(x, s):
    return x * s + sg(x * (1.0 - s))


def _floor_log2(x):
    _, ex = jnp.frexp(jnp.asarray(x, F32))
    return ex.astype(F32) - 1.0


def _ceil_log2(x):
    man, ex = jnp.frexp(jnp.asarray(x, F32))
    ex = ex.astype(F32)
    return jnp.where(man == 0.5, ex - 1.0, ex)


def int_bits(vmin, vmax):
    """Eq. 3: integer bits (sign excluded) that cover [vmin, vmax]."""
    vmin, vmax = sg(vmin), sg(vmax)
    hi = jnp.where(vmax > 0, _floor_log2(jnp.maximum(vmax, 1e-30)) + 1.0,
                   -127.0)
    lo = jnp.where(vmin < 0, _ceil_log2(jnp.maximum(-vmin, 1e-30)), -127.0)
    return jnp.maximum(hi, lo)


def weight_bits(w, f):
    """Per-channel bits of a [K, N] kernel (f [1, N]): relu(i' + f), with
    the regularizer's gradient on f scaled by 1/sqrt(K)."""
    fr = grad_scale(f, 1.0 / math.sqrt(w.shape[0]))
    return jax.nn.relu(int_bits(jnp.min(w, 0, keepdims=True),
                                jnp.max(w, 0, keepdims=True)) + fr)


def act_bits(f, vmin, vmax, n):
    """Per-tensor bits of an activation of n values, sign bit included
    where its range goes negative."""
    b = jax.nn.relu(int_bits(vmin, vmax) + grad_scale(f, 1.0 / math.sqrt(n)))
    return b + sg((vmin < 0).astype(F32)) * (b > 0)


def grid_exponent(amax, bits):
    """Largest f with amax * 2^f rounding inside +-(2^(bits-1) - 1)."""
    qmax = float(2 ** (bits - 1) - 1)
    fcap = _floor_log2(qmax / jnp.maximum(amax.astype(F32), 1e-12))
    return jnp.where(jnp.floor(amax * exp2i(fcap) + 0.5) > qmax,
                     fcap - 1.0, fcap)


def pack_dequant(w, f, bits):
    """The serving weight: ``w [..., K, N]`` on its channel's grid, the
    trained f capped so the channel's largest weight fits ``bits``."""
    w = w.astype(F32)
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    fi = jnp.minimum(jnp.floor(f + 0.5), grid_exponent(amax, bits))
    lo, hi = (-128.0, 127.0) if bits == 8 else \
        (-(2.0 ** (bits - 1) - 1), 2.0 ** (bits - 1) - 1)
    m = jnp.clip(jnp.floor(w * exp2i(fi) + 0.5), lo, hi)
    return m * exp2i(-fi)


def kv_store(x, bits):
    """A k or v row [..., hd] as it reads back from the quantized cache."""
    f = grid_exponent(jnp.max(jnp.abs(x), axis=-1, keepdims=True), bits)
    qmax = float(2 ** (bits - 1) - 1)
    return jnp.clip(jnp.round(x * exp2i(f)), -qmax, qmax) * exp2i(-f)


# ---------------------------------------------------------------- model --

SCALED_FP8 = "float8_e4m3fn_scaled"
E4M3 = jnp.float8_e4m3fn
E4M3_MAX = 448.0


def _fp8_round(x):
    """x on e4m3's grid after a per-tensor power-of-two scale that puts
    its largest magnitude inside e4m3's range (exact in float32)."""
    x = x.astype(F32)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = exp2i(_floor_log2(E4M3_MAX / amax))
    return (x * s).astype(E4M3).astype(F32) / s


def _fp8_operand(x):
    """Forward: the scaled e4m3 value; backward: straight through."""
    return sg(_fp8_round(x)) + (x.astype(F32) - sg(x.astype(F32)))


@jax.custom_vjp
def _fp8_cotangent(y):
    return y


_fp8_cotangent.defvjp(lambda y: (y, None),
                      lambda _, g: (_fp8_round(g),))


def _dot(spec, a, b, dtype):
    """einsum ``spec`` (None: a matmul) with operands in ``dtype``,
    products accumulated in float32; under ``SCALED_FP8`` the cotangent
    reaching it in the backward pass is put on the scaled e4m3 grid too,
    so both backward matmuls also take float8 operands."""
    if isinstance(dtype, str) and dtype == SCALED_FP8:
        a, b, dtype, wrap = _fp8_operand(a), _fp8_operand(b), F32, \
            _fp8_cotangent
    else:
        a, b, wrap = a.astype(dtype), b.astype(dtype), lambda y: y
    prec = jax.lax.Precision.HIGHEST if dtype == F32 else None
    if spec is None:
        return wrap(jnp.matmul(a, b, precision=prec,
                               preferred_element_type=F32))
    return wrap(jnp.einsum(spec, a, b, precision=prec,
                           preferred_element_type=F32))


def _mm(x, w, dtype):
    return _dot(None, x, w, dtype)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.log(theta) * (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[:, :, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _rms(x, scale, eps):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


class Weights:
    """How a mode reads a weight: training quantizes it with Algorithm 1,
    fp serving with Eq. 4, packed serving reads the plan's mantissas."""

    def __init__(self, mode: str, plan=None):
        self.mode, self.plan = mode, plan

    def kernel(self, kp, path):
        if self.mode == "train":
            return q_train(kp["w"], kp["f"])
        if self.plan is not None:
            return pack_dequant(kp["w"], kp["f"], self.plan(path))
        return q_eval(kp["w"], kp["f"])

    def vector(self, bp):
        fn = q_train if self.mode == "train" else q_eval
        return fn(bp["w"], bp["f"])

    def act(self, x, f):
        return (q_train if self.mode == "train" else q_eval)(x, f)


def forward(p, tokens, cfg, *, mode="eval", plan=None, kv_bits=None,
            dtype=F32):
    """Logits [B, S, V] of ``tokens [B, S]`` (positions 0..S-1, causal),
    and each activation quantizer's input extremes: name -> (min [L],
    max [L]), ``final_norm`` -> ([1], [1])."""
    z = sizes(cfg)
    B, S = tokens.shape
    H, KV, hd = z["H"], z["KV"], z["hd"]
    G = H // KV
    W = Weights(mode, plan)

    def span(y):
        return jnp.min(y.astype(F32)), jnp.max(y.astype(F32))

    table = W.kernel(p["embed"]["table"], "embed/table")
    x = jnp.take(table, tokens, axis=0)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, lp):
        ext = {}
        a, m = lp["attn"], lp["mlp"]
        h = _rms(x, lp["ln1"]["scale"], z["eps"])
        ext["ln1"] = span(h)
        h = W.act(h, lp["ln1"]["out_f"])
        proj = {}
        for name in ("wq", "wk", "wv"):
            y = _mm(h, W.kernel(a[name]["kernel"],
                                f"layers/attn/{name}/kernel"), dtype) \
                + W.vector(a[name]["bias"])
            ext[name] = span(y)
            proj[name] = W.act(y, a[name]["out_f"])
        qh = _rope(proj["wq"].reshape(B, S, H, hd), pos, z["theta"])
        kh = _rope(proj["wk"].reshape(B, S, KV, hd), pos, z["theta"])
        vh = proj["wv"].reshape(B, S, KV, hd)
        if kv_bits is not None:
            kh = kv_store(kh.astype(F32), kv_bits)
            vh = kv_store(vh.astype(F32), kv_bits)
        qg = qh.reshape(B, S, KV, G, hd)
        s = _dot("bskgh,btkh->bkgst", qg, kh, dtype) * (hd ** -0.5)
        s = jnp.where(causal, s, -1e30)
        pt = jnp.where(causal, jnp.exp(s - jnp.max(s, -1, keepdims=True)),
                       0.0)
        pt = W.act(pt, a["probs_f"])
        o = _dot("bkgst,btkh->bskgh", pt, vh, dtype)
        o = o / jnp.maximum(jnp.sum(pt, -1), 1e-20).transpose(
            0, 3, 1, 2)[..., None]
        o = o.reshape(B, S, H * hd)
        ext["attnout"] = span(o)
        o = W.act(o, a["attnout_f"])
        x = x + _mm(o, W.kernel(a["wo"]["kernel"], "layers/attn/wo/kernel"),
                    dtype)
        h = _rms(x, lp["ln2"]["scale"], z["eps"])
        ext["ln2"] = span(h)
        h = W.act(h, lp["ln2"]["out_f"])
        g = jax.nn.silu(_mm(h, W.kernel(m["gate"]["kernel"],
                                        "layers/mlp/gate/kernel"), dtype))
        ext["gate"] = span(g)
        g = W.act(g, m["gate"]["out_f"])
        u = _mm(h, W.kernel(m["up"]["kernel"], "layers/mlp/up/kernel"),
                dtype)
        ext["up"] = span(u)
        u = W.act(u, m["up"]["out_f"])
        x = x + _mm(g * u, W.kernel(m["down"]["kernel"],
                                    "layers/mlp/down/kernel"), dtype)
        return x, ext

    if mode == "train":          # recompute a layer in the backward pass
        layer = jax.checkpoint(layer)
    x, ext = jax.lax.scan(layer, x, p["layers"])
    h = _rms(x, p["final_norm"]["scale"], z["eps"])
    lo, hi = span(h)
    ext["final_norm"] = (lo[None], hi[None])
    h = W.act(h, p["final_norm"]["out_f"])
    return _mm(h, table.T, dtype), ext


# -------------------------------------------------------------- serving --

def plan_widths(plan: Dict):
    """path -> pack bits by the plan's deepest matching prefix."""
    layers = plan.get("layers", {})

    def width(path):
        parts = path.split("/")
        for n in range(len(parts), 0, -1):
            e = layers.get("/".join(parts[:n]))
            if e is not None:
                return e["pack_bits"]
        return plan["default"]["pack_bits"]
    return width


def kv_width(plan: Dict) -> int:
    return min([plan["default"]["kv_bits"]]
               + [e["kv_bits"] for e in plan.get("layers", {}).values()])


def serve_logits_fn(cfg: Dict, plan: Dict, dtype=F32):
    """jit(params, tokens [1, T]) -> logits [T, V] of the served model:
    packed weights at the plan's widths, the plan's KV width."""
    width, kvb = plan_widths(plan), kv_width(plan)

    def fn(p, tokens):
        return forward(p, tokens, cfg, mode="eval", plan=width, kv_bits=kvb,
                       dtype=dtype)[0][0]
    return jax.jit(fn)


# ------------------------------------------------------------- training --

def _ce(logits, tokens):
    lg = logits[:, :-1].astype(F32)
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, -1) - gold)


def regularizer(p, ranges, cfg, B, S):
    """(~EBOPs, L1) of one training forward at batch B x seq S, from the
    activation ranges after this batch's update."""
    z = sizes(cfg)
    L, d, H, KV, hd, ff, V = (z[k] for k in
                              ("L", "d", "H", "KV", "hd", "ff", "V"))
    n = B * S
    lay = p["layers"]
    a, m = lay["attn"], lay["mlp"]

    def ab(name, f, width):
        lo, hi = ranges[name]
        if f.ndim == 0:
            return act_bits(f, lo[0], hi[0], n * width)
        return jax.vmap(lambda fi, a_, b_: act_bits(fi, a_, b_, n * width)
                        )(f, lo, hi)

    def wsum(kp):       # per layer: sum over output channels of the bits
        return jax.vmap(lambda w, f: jnp.sum(weight_bits(w, f)))(
            kp["w"], kp["f"])

    b_ln1 = ab("ln1", lay["ln1"]["out_f"], d)
    b_q = ab("wq", a["wq"]["out_f"], H * hd)
    b_k = ab("wk", a["wk"]["out_f"], KV * hd)
    b_v = ab("wv", a["wv"]["out_f"], KV * hd)
    b_ao = ab("attnout", a["attnout_f"], H * hd)
    b_ln2 = ab("ln2", lay["ln2"]["out_f"], d)
    b_g = ab("gate", m["gate"]["out_f"], ff)
    b_u = ab("up", m["up"]["out_f"], ff)
    n_qk = float(B * H * S) * float(S) * hd
    eb = (b_ln1 * d * (wsum(a["wq"]["kernel"]) + wsum(a["wk"]["kernel"])
                       + wsum(a["wv"]["kernel"]))
          + b_q * b_k * n_qk
          + jax.nn.relu(1.0 + a["probs_f"]) * b_v * n_qk
          + b_ao * (H * hd) * wsum(a["wo"]["kernel"])
          + b_ln2 * d * (wsum(m["gate"]["kernel"]) + wsum(m["up"]["kernel"]))
          + (b_g + b_u) * ff * wsum(m["down"]["kernel"]))
    fn = p["final_norm"]
    tb = p["embed"]["table"]
    b_f = ab("final_norm", fn["out_f"], d)
    ebops = jnp.sum(eb) + b_f * V * jnp.sum(weight_bits(tb["w"], tb["f"]))
    acts = [lay["ln1"]["out_f"], a["wq"]["out_f"], a["wk"]["out_f"],
            a["wv"]["out_f"], a["attnout_f"], a["probs_f"],
            lay["ln2"]["out_f"], m["gate"]["out_f"], m["up"]["out_f"],
            fn["out_f"]]
    l1 = sum(jnp.sum(jax.nn.relu(f)) for f in acts)
    return ebops, l1


def new_ranges(old: Dict, ext: Dict) -> Dict:
    """The running extremes after one training batch."""
    return {k: (jnp.minimum(old[k][0] * RANGE_DECAY, ext[k][0]),
                jnp.maximum(old[k][1] * RANGE_DECAY, ext[k][1]))
            for k in ext}


def zero_ranges(cfg) -> Dict:
    L = sizes(cfg)["L"]
    out = {k: (jnp.zeros((L,), F32), jnp.zeros((L,), F32))
           for k in ("ln1", "wq", "wk", "wv", "attnout", "ln2", "gate",
                     "up")}
    out["final_norm"] = (jnp.zeros((1,), F32), jnp.zeros((1,), F32))
    return out


def leaf_norms(tree):
    """The L2 norm of every leaf, in ``jax.tree.leaves`` order."""
    return [float(x) for x in jax.jit(lambda t: [
        jnp.sqrt(jnp.sum(jnp.square(a.astype(F32))))
        for a in jax.tree.leaves(t)])(tree)]


def train_steps(p0, batches: List, cfg: Dict, tcfg: Dict, *, rows=2,
                dtype=F32, half_loss=False):
    """Run ``len(batches)`` HGQ training steps from ``p0`` in plain form.

    Each step: the batch's activation extremes (forward in blocks of
    ``rows``), the cross-entropy gradient summed over those blocks, the
    regularizer's gradient, global-norm clipping, AdamW.  Returns the
    losses, the leaf norms of the first step's clipped gradient and the
    leaf norms of the parameters' change over all the steps.  Buffers are
    donated step to step, so the peak is about five copies of the
    parameters plus one block's activations.

    ``half_loss`` plants a fault: the cross-entropy is the mean over the
    first half of the rows only, while the extremes and the regularizer
    still see the whole batch."""
    B, S = batches[0].shape
    nblk = B // rows
    nce = nblk // 2 if half_loss else nblk

    @jax.jit
    def extremes(p, toks):
        _, ext = forward(p, toks, cfg, mode="train", dtype=dtype)
        return ext

    def ce(p, toks):
        return _ce(forward(p, toks, cfg, mode="train", dtype=dtype)[0],
                   toks)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def ce_acc(g, p, toks):
        lv, gb = jax.value_and_grad(ce)(p, toks)
        return jax.tree.map(lambda a, b: a + b / nce, g, gb), lv

    def beta(step):
        if tcfg.get("beta_const") is not None:
            return tcfg["beta_const"]
        t = min(max(step / float(max(tcfg["steps"], 1)), 0.0), 1.0)
        l0, l1 = math.log(tcfg["beta0"]), math.log(tcfg["beta1"])
        return float(jnp.exp(F32(l0) + F32(t) * F32(l1 - l0)))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def reg_acc(g, p, ranges, b):
        def f(pp):
            eb, l1 = regularizer(pp, ranges, cfg, B, S)
            return b * eb + tcfg["gamma"] * l1
        return jax.tree.map(jnp.add, g, jax.grad(f)(p))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def update(p, g, mu, nu, t):
        leaves = jax.tree.leaves(g)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in leaves))
        scale = jnp.minimum(1.0, tcfg["clip_norm"] / jnp.maximum(gn, 1e-12))
        g = jax.tree.map(lambda x: x * scale, g)
        b1, b2 = 0.9, 0.999
        mu = jax.tree.map(lambda m_, x: b1 * m_ + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v_, x: b2 * v_ + (1 - b2) * x * x, nu, g)
        bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t

        def step(pp, m_, v_):
            dp = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + 1e-8)
            if tcfg["weight_decay"]:
                dp = dp + tcfg["weight_decay"] * pp
            return pp - tcfg["lr"] * dp
        return jax.tree.map(step, p, mu, nu), g, mu, nu

    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    p = jax.jit(lambda t: jax.tree.map(jnp.copy, t))(p0)
    mu, nu = zeros(p0), zeros(p0)
    ranges = zero_ranges(cfg)
    losses, g_first = [], None
    for i, toks in enumerate(batches):
        blocks = [toks[r * rows:(r + 1) * rows] for r in range(nblk)]
        ext = [extremes(p, blk) for blk in blocks]
        ext = {k: (jnp.min(jnp.stack([e[k][0] for e in ext]), 0),
                   jnp.max(jnp.stack([e[k][1] for e in ext]), 0))
               for k in ext[0]}
        ranges = new_ranges(ranges, ext)
        g, loss = zeros(p0), 0.0
        for blk in blocks[:nce]:
            g, lv = ce_acc(g, p, blk)
            loss += float(lv) / nce
        g = reg_acc(g, p, ranges, F32(beta(i)))
        p, g, mu, nu = update(p, g, mu, nu, F32(i + 1))
        losses.append(loss)
        if g_first is None:
            g_first = leaf_norms(g)
        del g
    change = leaf_norms(jax.tree.map(jnp.subtract, p, p0))
    return losses, g_first, change
