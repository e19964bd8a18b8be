"""Plain float32 reference of a Qwen3 decoder (Qwen/Qwen3-4B config.json),
with full causal softmax attention or with the paper's structured random
feature (SRF) attention (arXiv:1604.07356: circulant Spinner A·D1·H·D0,
positive softmax features exp(Wx - |x|^2/2)/sqrt(m)).

Written from the published description, not from the program: it imports
nothing of ``repro`` and takes only weights it makes itself from the seed
(``servebench/weights.py``). Every matrix product runs at HIGHEST precision
(float32 on the TPU). With ``fp8=True`` every product takes both operands
rounded to float8 e4m3 with one scale per tensor: the lower-precision
control that the correctness limit must reject.

Two witnesses of what a narrow type costs in SRF attention, used by
``calibrate.py`` and never by a run. With ``state_dtype`` set, SRF
attention runs in its recurrent form: the running sums (the state) are
rounded to ``state_dtype`` wherever a server stores them, after each
prefill chunk and after each decoded token (``hidden(..., stored=...)``).
With ``feature_dtype`` set, q, k and v are held in that type, the
projection takes operands of that type (float32 sums), and the features
are rounded to it.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from servebench.weights import WeightSpec, global_maker, layer_maker

HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 256          # query rows per attention block (bounds the scores)


def _sizes(c: Dict) -> Dict:
    p = c["published"]
    return {"d": p["hidden_size"], "h": p["num_attention_heads"],
            "kv": p["num_key_value_heads"], "hd": p["head_dim"],
            "ff": p["intermediate_size"], "vocab": p["vocab_size"],
            "layers": p["num_hidden_layers"], "eps": p["rms_norm_eps"],
            "theta": float(p["rope_theta"]),
            "tied": bool(p["tie_word_embeddings"])}


def program_sizes(config: Dict) -> Dict:
    """The program's ``ModelConfig`` attributes (dotted into nested ones)
    and the values the configuration says they must hold."""
    p = config["published"]
    want = {"n_layers": p["num_hidden_layers"], "d_model": p["hidden_size"],
            "n_heads": p["num_attention_heads"],
            "n_kv_heads": p["num_key_value_heads"], "head_dim": p["head_dim"],
            "d_ff": p["intermediate_size"], "vocab": p["vocab_size"],
            "rope_theta": float(p["rope_theta"]), "norm_eps": p["rms_norm_eps"],
            "tie_embeddings": p["tie_word_embeddings"], "qk_norm": True,
            "qkv_bias": p["attention_bias"], "dtype": config["dtype"],
            "attn_impl": config["attention"]["impl"]}
    srf = config["attention"].get("srf")
    if srf:
        want.update({"srf.kind": srf["kind"], "srf.n_features":
                     srf["n_features"], "srf.feature": srf["feature"]})
    return want


def weight_specs(config: Dict) -> List[WeightSpec]:
    s = _sizes(config)
    d, h, kv, hd, ff, v = s["d"], s["h"], s["kv"], s["hd"], s["ff"], s["vocab"]
    specs = [
        WeightSpec("embed", (v, d), False, "normal", 0.02),
        WeightSpec("final_norm", (d,), False, "norm", 0.05),
        WeightSpec("ln1", (d,), True, "norm", 0.05),
        WeightSpec("wq", (d, h * hd), True, "normal", d ** -0.5),
        WeightSpec("wk", (d, kv * hd), True, "normal", d ** -0.5),
        WeightSpec("wv", (d, kv * hd), True, "normal", d ** -0.5),
        WeightSpec("wo", (h * hd, d), True, "normal", (h * hd) ** -0.5),
        WeightSpec("q_norm", (hd,), True, "norm", 0.05),
        WeightSpec("k_norm", (hd,), True, "norm", 0.05),
        WeightSpec("ln2", (d,), True, "norm", 0.05),
        WeightSpec("w_gate", (d, ff), True, "normal", d ** -0.5),
        WeightSpec("w_up", (d, ff), True, "normal", d ** -0.5),
        WeightSpec("w_down", (ff, d), True, "normal", ff ** -0.5),
    ]
    if not s["tied"]:
        specs.append(WeightSpec("lm_head", (d, v), False, "normal", d ** -0.5))
    srf = config["attention"].get("srf")
    if srf:
        n, m = hd, srf["n_features"]
        specs += [WeightSpec("srf_g", (kv, -(-m // n), n), True, "normal", 1.0),
                  WeightSpec("srf_d0", (kv, n), True, "sign"),
                  WeightSpec("srf_d1", (kv, n), True, "sign")]
    return specs


def _q8(a):
    s = jnp.max(jnp.abs(a)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ein(eq, a, b, fp8: bool):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate the two halves of each head (positions 0..L-1 per row)."""
    _, l, _, hd = x.shape
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(l)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _hadamard(n: int) -> np.ndarray:
    h = np.ones((1, 1))
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    return h / math.sqrt(n)


def _srf_matrix(g, d0, d1, m: int):
    """(kv, m, n) projection A·diag(d1)·H·diag(d0), A block-circulant with
    rows A[b*n + i, j] = g[b, (j - i) mod n]."""
    kv, nb, n = g.shape
    i = np.arange(nb * n)
    idx = (np.arange(n)[None, :] - (i % n)[:, None]) % n        # (nb*n, n)
    a = g[:, (i // n)[:, None], idx][:, :m]                     # (kv, m, n)
    had = jnp.asarray(_hadamard(n), jnp.float32)
    return jnp.einsum("kmn,nj->kmj", a * d1[:, None, :], had,
                      precision=HI) * d0[:, None, :]


def _rnd(a, dtype):
    return a if dtype is None else a.astype(dtype).astype(jnp.float32)


def _phi(x, w, query: bool, fp8: bool, narrow=None):
    """x: (K, L, kv, [g,] hd) -> features (..., m). ``narrow``: the
    feature witness's type (see the module's docstring)."""
    hd = x.shape[-1]
    xs = _rnd(x * hd ** -0.25, narrow)
    eq = "blkgd,kmd->blkgm" if x.ndim == 5 else "blkd,kmd->blkm"
    if narrow is None:
        wx = _ein(eq, xs, w, fp8)
    else:
        wx = jnp.einsum(eq, xs.astype(narrow), w.astype(narrow),
                        preferred_element_type=jnp.float32)
    z = wx - 0.5 * jnp.sum(xs * xs, -1, keepdims=True)
    if query:
        z = z - jnp.max(z, -1, keepdims=True)
    return _rnd(jnp.exp(z) / math.sqrt(w.shape[1]), narrow)


def _srf_recurrent(phi_q, phi_k, v, stored, state_dtype):
    """Causal SRF attention as a scan over positions. phi_q (b, l, kv, g,
    m), phi_k (b, l, kv, m), v (b, l, kv, hd); stored (b, l): the state is
    rounded to ``state_dtype`` after that position. Between two stores the
    new terms are summed exactly beside the stored state."""
    def step(carry, xs):
        s, z, ps, pz = carry
        q, k, vv, st = xs
        ps = ps + jnp.einsum("bkm,bkd->bkmd", k, vv, precision=HI)
        pz = pz + k
        num = jnp.einsum("bkgm,bkmd->bkgd", q, s + ps, precision=HI)
        den = jnp.einsum("bkgm,bkm->bkg", q, z + pz, precision=HI)
        out = num / (den[..., None] + 1e-6)
        st4, st3 = st[:, None, None, None], st[:, None, None]
        s = jnp.where(st4, _rnd(s + ps, state_dtype), s)
        z = jnp.where(st3, _rnd(z + pz, state_dtype), z)
        return (s, z, jnp.where(st4, 0.0, ps), jnp.where(st3, 0.0, pz)), out

    b, _, kv, m = phi_k.shape
    hd = v.shape[-1]
    zs = jnp.zeros((b, kv, m, hd), jnp.float32)
    zz = jnp.zeros((b, kv, m), jnp.float32)
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (phi_q, phi_k, v, stored))
    _, outs = jax.lax.scan(step, (zs, zz, zs, zz), xs)
    return jnp.moveaxis(outs, 0, 1)                     # (b, l, kv, g, hd)


def _layer(w, x, s: Dict, srf: Dict, fp8: bool, stored=None,
           state_dtype=None, feature_dtype=None):
    b, l, _ = x.shape
    h, kv, hd = s["h"], s["kv"], s["hd"]
    g = h // kv
    y = _rms(x, w["ln1"], s["eps"])
    q = _ein("bld,de->ble", y, w["wq"], fp8).reshape(b, l, h, hd)
    k = _ein("bld,de->ble", y, w["wk"], fp8).reshape(b, l, kv, hd)
    v = _ein("bld,de->ble", y, w["wv"], fp8).reshape(b, l, kv, hd)
    q = _rope(_rms(q, w["q_norm"], s["eps"]), s["theta"])
    k = _rope(_rms(k, w["k_norm"], s["eps"]), s["theta"])
    qg = q.reshape(b, l, kv, g, hd)
    if srf:
        wp = _srf_matrix(w["srf_g"], w["srf_d0"], w["srf_d1"],
                         srf["n_features"])
        qg, k = (_phi(_rnd(qg, feature_dtype), wp, True, fp8, feature_dtype),
                 _phi(_rnd(k, feature_dtype), wp, False, fp8, feature_dtype))
        v = _rnd(v, feature_dtype)
    if srf and state_dtype is not None:
        o = _srf_recurrent(qg, k, v, stored, state_dtype)
        return _mlp_block(w, x, o.reshape(b, l, h * hd), s, fp8)
    outs = []
    for c0 in range(0, l, Q_CHUNK):
        qc = qg[:, c0:c0 + Q_CHUNK]
        rows = np.arange(c0, c0 + qc.shape[1])[:, None]
        mask = jnp.asarray(np.arange(l)[None, :] <= rows)      # (qc, L)
        sc = _ein("bqkgd,bjkd->bkgqj", qc, k, fp8)
        if srf:
            a = jnp.where(mask, sc, 0.0)
            num = _ein("bkgqj,bjkd->bqkgd", a, v, fp8)
            den = jnp.sum(a, -1).transpose(0, 3, 1, 2)[..., None]
            outs.append(num / (den + 1e-6))
        else:
            p = jax.nn.softmax(jnp.where(mask, sc / math.sqrt(hd), -jnp.inf),
                               -1)
            outs.append(_ein("bkgqj,bjkd->bqkgd", p, v, fp8))
    o = jnp.concatenate(outs, 1).reshape(b, l, h * hd)
    return _mlp_block(w, x, o, s, fp8)


def _mlp_block(w, x, o, s: Dict, fp8: bool):
    """The attention output projection and residual, then the MLP."""
    x = x + _ein("ble,ed->bld", o, w["wo"], fp8)
    y = _rms(x, w["ln2"], s["eps"])
    u = jax.nn.silu(_ein("bld,df->blf", y, w["w_gate"], fp8)) \
        * _ein("bld,df->blf", y, w["w_up"], fp8)
    return x + _ein("blf,fd->bld", u, w["w_down"], fp8)


class Reference:
    """Logits of the reference at chosen positions of whole sequences.

    ``config`` is the configuration file; ``key`` the weight key of the
    run's seed; ``storage`` the dtype the weights are served in (they are
    made in it, then widened to float32)."""

    def __init__(self, config: Dict, key, storage, fp8: bool = False,
                 state_dtype=None, feature_dtype=None):
        self.s = _sizes(config)
        self.srf = config["attention"].get("srf") or {}
        self.fp8 = fp8
        self.key = key
        specs = weight_specs(config)
        self._glob = global_maker(specs, storage)
        self._lay = layer_maker(specs, storage)
        self._step = jax.jit(partial(self._layer_f32, s=self.s, srf=self.srf,
                                     fp8=fp8, state_dtype=state_dtype,
                                     feature_dtype=feature_dtype))
        g = {k: v.astype(jnp.float32) for k, v in self._glob(key).items()}
        self.embed = g["embed"]
        self.final_norm = g["final_norm"]
        # tied: the output head is the embedding, read as (vocab, d)
        self.head, self.head_eq = ((g["lm_head"], "nd,dv->nv")
                                   if "lm_head" in g else
                                   (self.embed, "nd,vd->nv"))

    @staticmethod
    def _layer_f32(w, x, stored, s, srf, fp8, state_dtype, feature_dtype):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        return _layer(w, x, s, srf, fp8, stored, state_dtype, feature_dtype)

    def hidden(self, tokens: np.ndarray, rows: np.ndarray, stored=None):
        """tokens: (K, L) int32 sequences from position 0 (a padded tail is
        never seen by earlier positions); rows: (N, 2) of (sequence,
        position); stored: (K, L) bool, where the recurrent form rounds its
        state (``state_dtype`` only). Returns the final-normed hidden
        states (N, d)."""
        x = self.embed[jnp.asarray(tokens)]
        st = None if stored is None else jnp.asarray(stored, bool)
        for layer in range(self.s["layers"]):
            x = self._step(self._lay(self.key, jnp.int32(layer)), x, st)
        h = x[jnp.asarray(rows[:, 0]), jnp.asarray(rows[:, 1])]
        return _rms(h, self.final_norm, self.s["eps"])

    def logits(self, h):
        """(n, d) -> (n, vocab) float32."""
        return _ein(self.head_eq, h, self.head, self.fp8)
