"""Plain reference of the served split model: ``dense_decoder``.

A Llama/Qwen-style decoder written out in ``jax.numpy`` and float32 at
``highest`` matmul precision, with no cache, kernels or batching tricks:
RMSNorm, rotary embedding (half-split), grouped-query causal attention
(with the q/k/v bias where the configuration has one, at its initial
value 0), SiLU-gated MLP, RMSNorm and the LM head.  After layer
``split_after_layer`` the boundary tensor goes through the paper's
codec as the configuration states it (eq. 1): clip to ``[cmin, cmax]``,
quantize to ``n_levels`` uniform levels with pinned outer bins, and
reconstruct.  Entropy coding and framing are lossless, so they leave the
reconstruction alone.  ``boundary`` runs the layers up to the split,
``codec`` the codec and ``tail_logits`` the layers after it and the
head, so the check can hold each half of the served path to its own
reference.

The clipping range is calibrated here too, independently of the
program: the boundary activations of the configuration's calibration
batches, their mean and variance, the asymmetric-Laplace + leaky-ReLU
feature model fitted to them (paper eqs. 2-7) by numerical integration,
and the range that minimises the model's clipping + quantization error
(eqs. 9-11), all by quadrature and a generic optimiser.

The control (``low=True``) is the same computation with every matmul
operand rounded to float8 (e4m3), the precision below the bfloat16 the
configurations serve in.

Nothing here imports the program.  The weights are the benchmark's
seeded arrays, in the layout ``benchkit.model.make_weights`` draws.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(low: bool, spec: str, a, b):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if low:
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (B, S, H, hd), positions 0 .. S-1."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd // 2, dtype=np.float64)
                          / (hd // 2))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "low"))
def _layer(x, p, *, eps, theta, low):
    """One decoder layer over the whole (B, S, d) sequence, causal."""
    mm = functools.partial(_mm, low)
    a = p["attn"]
    h = _rms(x, p["norm1"]["scale"], eps)
    q = _rope(mm("bsd,dhk->bshk", h, a["wq"]) + a.get("bq", 0.0), theta)
    k = _rope(mm("bsd,dhk->bshk", h, a["wk"]) + a.get("bk", 0.0), theta)
    v = mm("bsd,dhk->bshk", h, a["wv"]) + a.get("bv", 0.0)
    b, s, nh, hd = q.shape
    g = nh // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    scores = mm("bqhk,bthk->bhqt", q, k) / math.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = mm("bhqt,bthk->bqhk", probs, v)
    x = x + mm("bqhk,hkd->bqd", attn, p["attn"]["wo"])
    h2 = _rms(x, p["norm2"]["scale"], eps)
    gate = mm("bsd,df->bsf", h2, p["mlp"]["w1"])
    up = mm("bsd,df->bsf", h2, p["mlp"]["w3"])
    return x + mm("bsf,fd->bsd", jax.nn.silu(gate) * up, p["mlp"]["w2"])


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(x, norm, w, *, eps, low):
    return _mm(low, "bsd,dv->bsv", _rms(x, norm, eps), w)


@functools.partial(jax.jit, static_argnames=("n_levels",))
def _codec(x, cmin, cmax, *, n_levels):
    """Paper eq. 1: clip, quantize with pinned outer bins, reconstruct."""
    delta = (cmax - cmin) / (n_levels - 1)
    q = jnp.floor((jnp.clip(x, cmin, cmax) - cmin) / delta + 0.5)
    return cmin + q * delta


def _layer_params(conf, weights, i):
    p = jax.tree.map(lambda a: a[i], weights["groups"][0]["layers"][0])
    if conf.get("qkv_bias"):
        # the bias at its initial value (Hugging Face's Qwen2 draws
        # Linear biases as zeros); the seeded weights carry no bias leaf
        p["attn"] = dict(p["attn"], **{
            f"b{n}": jnp.zeros(p["attn"][f"w{n}"].shape[1:], jnp.float32)
            for n in "qkv"})
    return p


def boundary(conf, weights, tokens, *, low=False):
    """Activations after layer ``split_after_layer``: (B, S, d) f32."""
    x = weights["embed"]["table"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(conf["split_after_layer"]):
        x = _layer(x, _layer_params(conf, weights, i),
                   eps=conf["rms_norm_eps"], theta=conf["rope_theta"],
                   low=low)
    return x


def codec(conf, x, crange):
    """The boundary codec as the configuration states it (eq. 1)."""
    return _codec(jnp.asarray(x, jnp.float32), jnp.float32(crange[0]),
                  jnp.float32(crange[1]), n_levels=conf["codec"]["n_levels"])


def tail_logits(conf, weights, recon, *, start=0, count=None, low=False):
    """Logits (B, count, V) at positions ``start .. start + count - 1`` of
    the layers after the boundary and the head, run on the boundary's
    reconstruction ``recon`` (B, S, d) from position 0."""
    x = jnp.asarray(recon, jnp.float32)
    for i in range(conf["split_after_layer"], conf["num_hidden_layers"]):
        x = _layer(x, _layer_params(conf, weights, i),
                   eps=conf["rms_norm_eps"], theta=conf["rope_theta"],
                   low=low)
    count = x.shape[1] - start if count is None else count
    x = x[:, start:start + count]
    return _head(x, weights["final_norm"]["scale"], weights["head"]["w"],
                 eps=conf["rms_norm_eps"], low=low)


# -- calibration -------------------------------------------------------------

def calibration_tokens(conf) -> list[np.ndarray]:
    """The configuration's calibration batches: Zipf-distributed token
    ids, one ``SeedSequence([data_seed, step])`` generator per batch."""
    c = conf["codec"]["calibration"]
    out = []
    for step in range(c["batches"]):
        rng = np.random.default_rng(np.random.SeedSequence(
            [c["data_seed"], step]))
        z = rng.zipf(c["zipf_a"], size=(c["batch"], c["seq_len"] + 1))
        out.append((z % conf["vocab_size"]).astype(np.int32)[:, :c["seq_len"]])
    return out


def _feature_pdf(lam, mu, kappa, slope):
    """Density of Y = leaky_relu(X), X ~ asymmetric Laplace (eq. 2)."""
    norm = lam / (kappa + 1.0 / kappa)

    def fx(x):
        if x < mu:
            return norm * math.exp(lam * (x - mu) / kappa)
        return norm * math.exp(-lam * kappa * (x - mu))

    def fy(y):
        return fx(y) if y >= 0 else fx(y / slope) / slope

    kinks = sorted({0.0, mu if mu > 0 else slope * mu})
    return fy, kinks


def _integrate(fn, fy, kinks, lo, hi):
    from scipy import integrate
    cuts = [lo] + [k for k in kinks if lo < k < hi] + [hi]
    return sum(integrate.quad(lambda y: fn(y) * fy(y), a, b,
                              epsabs=1e-14, epsrel=1e-12, limit=200)[0]
               for a, b in zip(cuts[:-1], cuts[1:]))


def fit_feature_model(mean, var, kappa, slope):
    """(lam, mu) whose model mean and variance equal the sample's."""
    from scipy import optimize

    def resid(p):
        lam, mu = math.exp(p[0]), p[1]
        fy, kinks = _feature_pdf(lam, mu, kappa, slope)
        m1 = _integrate(lambda y: y, fy, kinks, -math.inf, math.inf)
        m2 = _integrate(lambda y: y * y, fy, kinks, -math.inf, math.inf)
        return [m1 - mean, (m2 - m1 * m1) - var]

    sol = optimize.least_squares(resid, x0=[0.0, -1.0], xtol=1e-15,
                                 ftol=1e-15, gtol=1e-15)
    return math.exp(sol.x[0]), float(sol.x[1])


def model_error(fy, kinks, cmin, cmax, n_levels):
    """Clipping + quantization error of the pinned uniform quantizer."""
    delta = (cmax - cmin) / (n_levels - 1)
    err = _integrate(lambda y: (y - cmin) ** 2, fy, kinks, -math.inf, cmin)
    err += _integrate(lambda y: (y - cmax) ** 2, fy, kinks, cmax, math.inf)
    for i in range(n_levels):
        lo = max(cmin, cmin + (i - 0.5) * delta)
        hi = min(cmax, cmin + (i + 0.5) * delta)
        r = cmin + i * delta
        err += _integrate(lambda y, r=r: (y - r) ** 2, fy, kinks, lo, hi)
    return err


def clip_range(mean, var, codec: dict) -> tuple[float, float]:
    """The model's error-minimising (cmin, cmax) for the codec's levels."""
    from scipy import optimize

    lam, mu = fit_feature_model(mean, var, codec["kappa"],
                                codec["leaky_slope"])
    fy, kinks = _feature_pdf(lam, mu, codec["kappa"], codec["leaky_slope"])
    n = codec["n_levels"]
    # the best cmax with cmin pinned at 0 starts the joint search
    c0 = optimize.minimize_scalar(
        lambda c: model_error(fy, kinks, 0.0, c, n), bounds=(1e-3, 100.0),
        method="bounded", options={"xatol": 1e-10}).x
    if codec["constrain_cmin_zero"]:
        return 0.0, float(c0)
    res = optimize.minimize(
        lambda p: model_error(fy, kinks, p[0], p[1], n)
        if p[1] > p[0] else math.inf,
        x0=np.array([0.0, c0]), method="Nelder-Mead",
        options={"xatol": 1e-10, "fatol": 1e-16, "maxiter": 4000})
    lo, hi = sorted(float(v) for v in res.x)
    return lo, hi


def calibrate(conf, weights, *, low=False) -> tuple[float, float]:
    """Clip range from the calibration batches' boundary activations."""
    total = total_sq = 0.0
    count = 0
    for toks in calibration_tokens(conf):
        x = np.asarray(boundary(conf, weights, toks, low=low), np.float64)
        total += x.sum()
        total_sq += (x * x).sum()
        count += x.size
    mean = total / count
    var = total_sq / count - mean * mean
    return clip_range(mean, var, conf["codec"])
