"""Plain float32 reference for a CMoE-converted dense GQA/SwiGLU model.

It follows the published descriptions and imports nothing of the
program under test:

- the decoder is the Llama/Qwen2/Mistral block: pre-RMSNorm (the served
  weights keep the norm gain as ``1 + scale``), GQA attention with
  rotary embeddings (split-half convention, base ``rope_theta``),
  optional q/k/v bias, causal softmax attention at scale head_dim^-1/2,
  and a SwiGLU FFN; a final RMSNorm and the (tied or separate) head;
- the FFN is converted as the CMoE paper (arXiv:2502.04416, Eq. 4 and
  Eq. 8) defines it: its hidden neurons are partitioned into shared
  experts and routed experts; every token keeps the shared neurons and
  the neurons of the ``top_k`` routed experts whose representative
  neuron's activation is largest; training-free gates are 1 (learnable
  scale u = 0, balance bias 0). The reference computes the full hidden
  layer and masks it, which is the same function as slicing the experts
  out and summing them.

Its inputs are the dense weights (which ``make_params`` makes from the
seed, exactly as the benchmark hands them to the program) and the
partition the conversion chose (index sets, per layer). Matmuls run at
``highest`` precision. ``quant="fp8"`` is the control, the reference
computed in the next precision below the configuration's bfloat16: every
tensor it stores (matmul operands and results, q/k/v, the FFN hidden,
the residual stream, the logits) is rounded to float8_e4m3fn with one
dynamic scale per tensor, as bfloat16 serving stores every tensor in
bfloat16; matmuls accumulate and softmax and norms compute in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

E4M3_MAX = 448.0


def dims(config: dict) -> dict:
    """The architecture numbers, from the configuration's published keys."""
    d = int(config["hidden_size"])
    h = int(config["num_attention_heads"])
    return {
        "L": int(config["num_hidden_layers"]),
        "d": d,
        "H": h,
        "KH": int(config["num_key_value_heads"]),
        "hd": int(config.get("head_dim") or d // h),
        "dff": int(config["intermediate_size"]),
        "V": int(config["vocab_size"]),
        "tied": bool(config["tie_word_embeddings"]),
        "bias": bool(config["attention_bias"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
    }


def seed32(seed: int) -> int:
    """A 31-bit PRNG seed from any whole number."""
    return int(np.random.default_rng(int(seed)).integers(0, 2 ** 31 - 1))


@functools.partial(jax.jit, static_argnames=("dm", "dtype"))
def _make(key, dm, dtype):
    dm = dict(dm)

    def _normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
            dtype)

    L, d, H, KH, hd, dff, V = (dm[k] for k in
                               ("L", "d", "H", "KH", "hd", "dff", "V"))
    ks = iter(jax.random.split(key, 16))
    attn = {"wq": _normal(next(ks), (L, d, H, hd), d ** -0.5),
            "wk": _normal(next(ks), (L, d, KH, hd), d ** -0.5),
            "wv": _normal(next(ks), (L, d, KH, hd), d ** -0.5),
            "wo": _normal(next(ks), (L, H, hd, d), (H * hd) ** -0.5)}
    if dm["bias"]:
        attn["bq"] = _normal(next(ks), (L, H, hd), 0.1)
        attn["bk"] = _normal(next(ks), (L, KH, hd), 0.1)
        attn["bv"] = _normal(next(ks), (L, KH, hd), 0.1)
    blocks = {"norm1": _normal(next(ks), (L, d), 0.1),
              "attn": attn,
              "norm2": _normal(next(ks), (L, d), 0.1),
              "ffn": {"wg": _normal(next(ks), (L, d, dff), d ** -0.5),
                      "wu": _normal(next(ks), (L, d, dff), d ** -0.5),
                      "wd": _normal(next(ks), (L, dff, d), dff ** -0.5)}}
    params = {"embed": _normal(next(ks), (V, d), d ** -0.5),
              "final_norm": _normal(next(ks), (d,), 0.1),
              "blocks": blocks}
    if not dm["tied"]:
        params["lm_head"] = _normal(next(ks), (d, V), d ** -0.5)
    return params


def make_params(config: dict, seed: int) -> dict:
    """The dense weights of ``config`` from ``seed`` in its stated dtype,
    made on the device in one jitted call, in the pytree layout the
    program serves."""
    return _make(jax.random.PRNGKey(seed32(seed)),
                 tuple(sorted(dims(config).items())),
                 jnp.dtype(config["torch_dtype"]))


# ----------------------------------------------------------------- forward

def _q8(a):
    """Round to float8_e4m3fn with one dynamic per-tensor scale."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / E4M3_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _r(a, quant):
    """A stored intermediate: rounded to fp8 in the control."""
    return _q8(a) if quant == "fp8" else a


def _mm(a, b, quant):
    a = _r(a.astype(jnp.float32), quant)
    b = _r(b.astype(jnp.float32), quant)
    return _r(jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST), quant)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs          # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attn(x, lp, dm, quant):
    """The attention half of a decoder layer over one sequence x (T, d)
    float32: x plus the attention output."""
    t = x.shape[0]
    H, KH, hd, d = dm["H"], dm["KH"], dm["hd"], dm["d"]
    a = lp["attn"]
    xn = _rms(x, lp["norm1"], dm["eps"])
    q = _mm(xn, a["wq"].reshape(d, H * hd), quant).reshape(t, H, hd)
    k = _mm(xn, a["wk"].reshape(d, KH * hd), quant).reshape(t, KH, hd)
    v = _mm(xn, a["wv"].reshape(d, KH * hd), quant).reshape(t, KH, hd)
    if dm["bias"]:
        q = q + a["bq"].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    pos = jnp.arange(t)
    q = _r(_rope(q, pos, dm["theta"]), quant)
    k = _r(_rope(k, pos, dm["theta"]), quant)
    v = _r(v, quant)
    g = H // KH
    qg = q.reshape(t, KH, g, hd)
    s = jnp.einsum("qkgh,skh->kgqs", qg, k,
                   precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s,
                  -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgqs,skh->qkgh", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(t, H * hd)
    return _r(x + _mm(o, a["wo"].reshape(H * hd, d), quant), quant)


def _ffn_hidden(x, lp, dm, quant):
    """The SwiGLU hidden layer (T, d_ff) of the FFN half."""
    f = lp["ffn"]
    xn = _rms(x, lp["norm2"], dm["eps"])
    gt = _mm(xn, f["wg"], quant)
    return _r(gt * jax.nn.sigmoid(gt) * _mm(xn, f["wu"], quant), quant)


@functools.partial(jax.jit, static_argnames=("dm", "top_k", "quant"))
def _layer(x, lp, shared_mask, owner, rep_idx, *, dm, top_k, quant):
    """One converted decoder layer over one sequence x (T, d) float32."""
    dm = dict(dm)
    t = x.shape[0]
    x = _attn(x, lp, dm, quant)
    h = _ffn_hidden(x, lp, dm, quant)
    scores = h[:, rep_idx]                                  # Eq. 8 router
    _, top = jax.lax.top_k(scores, top_k)                   # (T, k)
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(t)[:, None], top].set(True)              # (T, N_r)
    keep = shared_mask[None, :] | (chosen[:, jnp.maximum(owner, 0)] &
                                   (owner >= 0)[None, :])
    return _r(x + _mm(h * keep, lp["ffn"]["wd"], quant), quant)


@functools.partial(jax.jit, static_argnames=("dm", "k_act"))
def _dense_layer_profile(x, lp, *, dm, k_act):
    """One DENSE decoder layer over a batch x (B, T, d) float32, and its
    ATopK profile: which ``k_act`` hidden neurons have the largest |h|
    at each token (paper Eq. 14), as a (B * T, d_ff) bool matrix."""
    dm = dict(dm)

    def one(xs):
        xs = _attn(xs, lp, dm, None)
        h = _ffn_hidden(xs, lp, dm, None)
        return _r(xs + _mm(h, lp["ffn"]["wd"], None), None), h

    x, h = jax.vmap(one)(x)
    h = h.reshape(-1, h.shape[-1])
    _, idx = jax.lax.top_k(jnp.abs(h), k_act)
    a = jnp.zeros(h.shape, bool).at[
        jnp.arange(h.shape[0])[:, None], idx].set(True)
    return x, a


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _head(x, final_norm, head, picks, *, dm, quant):
    """(row max, logit of each pick) of the final-normed rows x (n, d);
    picks (m, n) token ids."""
    dm = dict(dm)
    xn = _rms(x, final_norm, dm["eps"])
    w = head.T if dm["tied"] else head
    logits = _mm(xn, w, quant)                              # (n, V)
    return logits.max(-1), jnp.take_along_axis(
        logits[None], picks[..., None], axis=-1)[..., 0], logits.argmax(-1)


def _layer_inputs(part: dict, dff: int):
    shared = np.zeros(dff, bool)
    shared[np.asarray(part["shared_idx"])] = True
    owner = np.full(dff, -1, np.int32)
    for e, idx in enumerate(np.asarray(part["routed_idx"])):
        owner[idx] = e
    return (jnp.asarray(shared), jnp.asarray(owner),
            jnp.asarray(np.asarray(part["rep_idx"], np.int32)))


def hidden(params, parts, config, tokens, *, pad_to, quant=None):
    """Final-layer residual stream (T, d) float32 of one sequence, padded
    to ``pad_to`` positions (causal: padding never reaches a real row)."""
    dm = dims(config)
    key = tuple(sorted(dm.items()))
    top_k = int(config["cmoe"]["top_k"])
    toks = np.zeros(pad_to, np.int32)
    toks[:len(tokens)] = tokens
    x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
    for li in range(dm["L"]):
        lp = jax.tree.map(lambda a: a[li], params["blocks"])
        x = _layer(x, lp, *_layer_inputs(parts[li], dm["dff"]), dm=key,
                   top_k=top_k, quant=quant)
    return x


def head_rows(params, config, x, picks, *, quant=None, block=256):
    """For rows x (n, d): (max logit (n,), logits of picks (m, n),
    argmax (n,)), computed ``block`` rows at a time."""
    dm = tuple(sorted(dims(config).items()))
    head = params["embed"] if dims(config)["tied"] else params["lm_head"]
    picks = np.asarray(picks, np.int32).reshape(-1, x.shape[0])
    n = x.shape[0]
    outs = []
    for s in range(0, n, block):
        xb = x[s:s + block]
        pb = picks[:, s:s + block]
        if xb.shape[0] < block:          # one compiled shape
            pad = block - xb.shape[0]
            xb = jnp.pad(xb, ((0, pad), (0, 0)))
            pb = np.pad(pb, ((0, 0), (0, pad)))
        mx, pk, am = _head(xb, params["final_norm"], head, jnp.asarray(pb),
                           dm=dm, quant=quant)
        k = min(block, n - s)
        outs.append((np.asarray(mx)[:k], np.asarray(pk)[:, :k],
                     np.asarray(am)[:k]))
    return (np.concatenate([o[0] for o in outs]),
            np.concatenate([o[1] for o in outs], axis=1),
            np.concatenate([o[2] for o in outs]))


def served_gaps(params, parts, config, prompt, generated, *, pad_to,
                control=False):
    """Widest gaps of one served request against the float32 reference.

    Returns {"served": max over the served tokens of (reference best
    logit - reference logit of the served token)} and, with ``control``,
    also {"control": the same gap for the token the fp8 reference puts
    first at each of those positions}."""
    seq = list(prompt) + list(generated[:-1])
    rows = np.arange(len(prompt) - 1, len(seq))
    x = hidden(params, parts, config, seq, pad_to=pad_to)[rows]
    picks = [list(generated)]
    if control:
        xc = hidden(params, parts, config, seq, pad_to=pad_to,
                    quant="fp8")[rows]
        _, _, ctop = head_rows(params, config, xc, [list(generated)],
                               quant="fp8")
        picks.append(list(ctop))
    mx, pk, _ = head_rows(params, config, x, picks)
    out = {"served": float(np.max(mx - pk[0]))}
    if control:
        out["control"] = float(np.max(mx - pk[1]))
    return out


# ------------------------------------------------------------ partition

def calib_tokens(config: dict, seed: int) -> np.ndarray:
    """The calibration batch (samples, seq) the conversion profiles,
    drawn from the seed."""
    c = config["cmoe"]
    rng = np.random.default_rng([int(seed), 1])
    return rng.integers(0, int(config["vocab_size"]),
                        (int(c["calib_samples"]), int(c["calib_seq"])))


def activation_profile(params, config, tokens) -> list:
    """Per layer, the dense model's ATopK matrix (q, d_ff) bool over the
    calibration tokens (samples, seq), from a float32 forward."""
    dm = tuple(sorted(dims(config).items()))
    k_act = int(config["cmoe"]["k_activation"])
    x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    out = []
    with jax.default_matmul_precision("highest"):
        for li in range(dims(config)["L"]):
            lp = jax.tree.map(lambda a: a[li], params["blocks"])
            x, a = _dense_layer_profile(x, lp, dm=dm, k_act=k_act)
            out.append(np.asarray(a))
    return out


def _sqdist(f, c):
    return ((f - c[None, :]) ** 2).sum(axis=1)


def partition_readings(profile: list, parts: list, config: dict) -> dict:
    """How far the served partition lies from the paper's construction
    (section 4.1), read against the reference's own activation profile.

    - ``invalid``: neurons out of place: the shared experts not holding
      exactly num_shared * m neurons, a routed expert not holding m, a
      neuron in two experts or in none, a representative neuron outside
      its expert (m = d_ff / num_experts);
    - ``shared_shortfall``: worst layer's share of the activation-rate
      mass (Eq. 15) of the reference's top num_shared * m neurons that
      the served shared experts miss;
    - ``cluster_gain``: the share of the routed neurons' activation-
      pattern scatter that the served routed experts remove (1 - within-
      expert / whole-pool sum of squares, both summed over the layers),
      as a multiple of the share a random balanced grouping removes on
      average, (N_r - 1) / (n - 1) for n neurons in N_r experts: the
      balanced k-means of section 4.1 reads above 1, an arbitrary
      grouping about 1. Summed, not per layer: in deep layers of a model
      with random weights a few neurons win every token's ATopK, the
      routed pool barely fires, and no grouping removes more than chance;
    - ``rep_rank``: mean over experts and layers of the share of an
      expert's neurons that lie closer to its centroid (Eq. 7) than its
      representative neuron does."""
    c = config["cmoe"]
    dff = int(config["intermediate_size"])
    n_e, n_s = int(c["num_experts"]), int(c["num_shared"])
    m = dff // n_e
    invalid, shortfall, ranks = 0, 0.0, []
    within_all = total_all = 0.0
    for a, part in zip(profile, parts):
        shared = np.asarray(part["shared_idx"]).reshape(-1)
        routed = np.asarray(part["routed_idx"])
        reps = np.asarray(part["rep_idx"]).reshape(-1)
        count = np.zeros(dff, np.int64)
        np.add.at(count, shared, 1)
        np.add.at(count, routed.reshape(-1), 1)
        invalid += int(np.abs(count - 1).sum())
        invalid += abs(shared.size - n_s * m)
        invalid += int(sum(abs(len(e) - m) for e in routed))
        invalid += abs(routed.shape[0] - (n_e - n_s)) + abs(
            reps.size - routed.shape[0])
        mu = a.mean(axis=0)
        best = np.sort(mu)[::-1][:n_s * m].sum()
        shortfall = max(shortfall, float(1 - mu[shared].sum() / best))
        feats = a.T.astype(np.float32)                       # (d_ff, q)
        pool = feats[routed.reshape(-1)]
        total = _sqdist(pool, pool.mean(axis=0)).sum()
        within = 0.0
        for e, rep in zip(routed, reps):
            f = feats[e]
            d = _sqdist(f, f.mean(axis=0))
            within += d.sum()
            hit = np.nonzero(e == rep)[0]
            if hit.size != 1:
                invalid += 1
                continue
            ranks.append(float((d < d[hit[0]]).mean()))
        within_all += within
        total_all += total
    n, k = routed.size, routed.shape[0]
    gain = (1 - within_all / max(total_all, 1e-30)) / ((k - 1) / (n - 1))
    return {"invalid": invalid, "shared_shortfall": shortfall,
            "cluster_gain": float(gain),
            "rep_rank": float(np.mean(ranks)) if ranks else 1.0}
