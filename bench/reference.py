"""Plain reference of the served model, in jax.numpy.

It imports nothing of the program.  From the benchmark it takes the seeded
weights (``weights.draw``) and the configuration file; it calibrates its own
readout windows on the seeded calibration batch, as the program's
``calibrate`` pass does, and runs full causal attention over whole
sequences: no paging, no chunking, no batching across requests.

The model (Qwen1.5 / Qwen2.5 layer equations): token embedding; per layer
``x += attn(rmsnorm(x))`` then ``x += ffn(rmsnorm(x))``; final rmsnorm; a
head tied to the embedding or its own matrix.  Attention has q/k/v biases,
rotary embeddings (rotate-half, base ``rope_theta``) and grouped KV heads;
the FFN is SiLU-gated.  Each analog site is the time-domain multiplier of
arXiv:1711.10673 in closed form: inputs to ``bits``-bit signed time codes per
row, weights to ``weight_bits``-bit signed current codes per column, an exact
integer charge sum, the latch normalisation, a ``bits``-bit readout over the
site's calibrated window, and the digital rescale.

Values are held in the configuration's precision (``torch_dtype``,
bfloat16) wherever the configuration keeps them: weights, the residual
stream, norm outputs, site outputs, q/k/v, attention scores, probabilities
and outputs, logits.  Everything between those points (norms, rotary
angles, softmax, every matmul's accumulation) runs in float32, matmuls at
the highest precision.  ``precision="fp8"`` is the control: the same model
with those values held in float8 e4m3, the step below bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as wts

Q_BLOCK = 512


def dims(cfg: dict) -> dict:
    """Shapes the reference needs, from the configuration file's keys."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    pad = cfg["vocab_pad_multiple"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"], "hd": hd,
            "f": cfg["intermediate_size"], "layers": cfg["num_hidden_layers"],
            "vocab": cfg["vocab_size"], "vp": -(-cfg["vocab_size"] // pad) * pad,
            "tied": bool(cfg["tie_word_embeddings"]),
            "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
            "bits": cfg["tdvmm"]["bits"], "wbits": cfg["tdvmm"]["weight_bits"]}


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Path -> shape of every weight, in the program's tree layout (the
    harness checks the program's tree against this before a run)."""
    m = dims(cfg)
    d, nl, q, kv = m["d"], m["layers"], m["h"] * m["hd"], m["kv"] * m["hd"]
    blk = "blocks/seg0/"
    out = {"embed/table": (m["vp"], d), "ln_f/scale": (d,),
           blk + "ln1/scale": (nl, d), blk + "ln2/scale": (nl, d),
           blk + "attn/wq/w": (nl, d, q), blk + "attn/wq/b": (nl, q),
           blk + "attn/wk/w": (nl, d, kv), blk + "attn/wk/b": (nl, kv),
           blk + "attn/wv/w": (nl, d, kv), blk + "attn/wv/b": (nl, kv),
           blk + "attn/wo/w": (nl, q, d),
           blk + "ffn/w_gate/w": (nl, d, m["f"]),
           blk + "ffn/w_up/w": (nl, d, m["f"]),
           blk + "ffn/w_down/w": (nl, m["f"], d)}
    if not m["tied"]:
        out["head/w"] = (d, m["vp"])
    return out


def _held(dtype):
    """Round a float32 value to where the configuration holds it."""
    def rnd(x):
        return x.astype(dtype).astype(jnp.float32)
    rnd.__name__ = f"held_{jnp.dtype(dtype).name}"
    return rnd


PRECISIONS = {"bfloat16": _held(jnp.bfloat16), "fp8": _held(jnp.float8_e4m3fn)}


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x: (S, H, D); rotate-half convention."""
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _site(x, w, window, bits, wbits):
    """The analog multiplier on x (M, K) and w (K, N).  ``window=None``
    calibrates the readout on this call's own max|z|.  Returns (y, max|z|)."""
    lx, lw = (1 << bits) - 1, (1 << wbits) - 1
    k = x.shape[-1]
    sx = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-6)
    xc = jnp.round(jnp.clip(x / sx, -1.0, 1.0) * lx).astype(jnp.int8)
    wm = jnp.maximum(jnp.max(jnp.abs(w), 0, keepdims=True), 1e-6)
    wc = jnp.round(jnp.clip(w / wm, -1.0, 1.0) * lw).astype(jnp.int8)
    acc = jnp.dot(xc, wc, preferred_element_type=jnp.int32)
    z = acc.astype(jnp.float32) * jnp.float32(1.0 / (lx * lw * 2.0 * k))
    zmax = jnp.max(jnp.abs(z))
    win = jnp.maximum(zmax, 1e-9) if window is None else window
    q = jnp.round(jnp.clip(z / win, -1.0, 1.0) * lx)
    return q * (win / lx) * (2.0 * k) * sx * wm, zmax


def _attend(q, k, v, length, rnd):
    """Causal attention of one sequence.  q: (S, H, D), k/v: (S, KV, D);
    keys at or past ``length`` (padding) are masked; query blocks of
    ``Q_BLOCK`` keep the score matrix small."""
    s, h, dh = q.shape
    g = h // k.shape[1]
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    bq = min(Q_BLOCK, s)
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq)
        qpos = i * bq + jnp.arange(bq)
        sc = rnd(jnp.einsum("qhd,khd->hqk", qb, kk)) * dh ** -0.5
        mask = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < length)
        p = rnd(jax.nn.softmax(jnp.where(mask[None], sc, -1e30), -1))
        return rnd(jnp.einsum("hqk,khd->qhd", p, vv))

    out = jax.lax.map(block, jnp.arange(s // bq))
    return out.reshape(s, h, dh)


@functools.partial(jax.jit, static_argnames=("m", "calib", "rnd"))
def _layer(x, lens, lw, win, *, m, calib, rnd):
    """One layer over rows of x (R, S, d).  ``calib``: readout windows come
    from each call's own data (the calibration pass).  Returns (x, zmax)."""
    m = dict(m)
    lw = {k: rnd(v.astype(jnp.float32)) for k, v in lw.items()}
    bits, wbits = m["bits"], m["wbits"]
    r, s, d = x.shape
    site = functools.partial(_site, bits=bits, wbits=wbits)

    def w_(name):
        return None if calib else win[name]

    h = rnd(_rmsnorm(x, lw["ln1"], m["eps"])).reshape(r * s, d)
    qkv, z_qkv = [], []
    for j, (wn, bn) in enumerate((("wq", "bq"), ("wk", "bk"), ("wv", "bv"))):
        y, z = site(h, lw[wn], None if calib else win["attn.qkv"][j])
        qkv.append(rnd(rnd(y) + lw[bn]))
        z_qkv.append(z)
    pos = jnp.arange(s)
    q = qkv[0].reshape(r, s, m["h"], m["hd"])
    k = qkv[1].reshape(r, s, m["kv"], m["hd"])
    v = qkv[2].reshape(r, s, m["kv"], m["hd"])
    rope = jax.vmap(lambda t: rnd(_rope(t, pos, m["theta"])))
    q, k = rope(q), rope(k)
    att = jax.vmap(lambda a, b, c, n: _attend(a, b, c, n, rnd))(q, k, v, lens)
    o, z_out = site(att.reshape(r * s, -1), lw["wo"], w_("attn.out"))
    x = rnd(x + rnd(o).reshape(r, s, d))
    h = rnd(_rmsnorm(x, lw["ln2"], m["eps"])).reshape(r * s, d)
    gate, z_g = site(h, lw["w_gate"], w_("ffn.in"))
    up, z_u = site(h, lw["w_up"], w_("ffn.in"))
    gate = rnd(gate)
    act = rnd(rnd(gate * rnd(jax.nn.sigmoid(gate))) * rnd(up))
    dn, z_d = site(act, lw["w_down"], w_("ffn.out"))
    x = rnd(x + rnd(dn).reshape(r, s, d))
    zmax = {"attn.qkv": jnp.stack(z_qkv), "attn.out": z_out,
            "ffn.in": jnp.maximum(z_g, z_u), "ffn.out": z_d}
    return x, zmax


@functools.partial(jax.jit, static_argnames=("m", "calib", "rnd"))
def _head(x, ln_f, hw, win, *, m, calib, rnd):
    """Final norm and head on rows x (N, d): logits (N, vocab), max|z|."""
    m = dict(m)
    ln_f, hw = (rnd(a.astype(jnp.float32)) for a in (ln_f, hw))
    h = rnd(_rmsnorm(x, ln_f, m["eps"]))
    if m["tied"]:
        logits, z = h @ hw.T, jnp.float32(0.0)
    else:
        logits, z = _site(h, hw, None if calib else win["head"],
                          m["bits"], m["wbits"])
    return rnd(logits)[:, :m["vocab"]], z


class Reference:
    """The reference model for one configuration and seed.  Weights are
    drawn on the device as the configuration states them (bfloat16) and
    held there until the object is dropped."""

    def __init__(self, cfg: dict, seed: int, precision: str | None = None):
        self.m = dims(cfg)
        self.mkey = tuple(sorted(self.m.items()))
        self.rnd = PRECISIONS[precision or cfg["torch_dtype"]]
        key = wts.base_key(seed)
        dt = jnp.bfloat16
        shapes = param_shapes(cfg)
        draw = jax.jit(wts.draw, static_argnums=(1, 2, 3))
        self.w = {p: draw(key, p, s, dt) for p, s in shapes.items()}
        self.windows = None

    def _layer_weights(self, i: int) -> dict:
        blk = "blocks/seg0/"
        names = {"ln1": "ln1/scale", "ln2": "ln2/scale",
                 "wq": "attn/wq/w", "bq": "attn/wq/b", "wk": "attn/wk/w",
                 "bk": "attn/wk/b", "wv": "attn/wv/w", "bv": "attn/wv/b",
                 "wo": "attn/wo/w", "w_gate": "ffn/w_gate/w",
                 "w_up": "ffn/w_up/w", "w_down": "ffn/w_down/w"}
        return {k: self.w[blk + p][i] for k, p in names.items()}

    def _embed(self, tokens):
        return self.rnd(self.w["embed/table"][tokens].astype(jnp.float32))

    def _head_w(self):
        hw = self.w["embed/table"] if self.m["tied"] else self.w["head/w"]
        return self.w["ln_f/scale"], hw

    def calibrate(self, tokens: np.ndarray) -> dict:
        """Readout windows from the calibration batch (rows, length): the
        max|z| of each site over every layer and row; the head's over each
        row's last position, as the program's calibration pass takes it."""
        mk = self.mkey
        x = self._embed(jnp.asarray(tokens))
        lens = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
        acc: dict = {}
        for i in range(self.m["layers"]):
            x, z = _layer(x, lens, self._layer_weights(i), None,
                          m=mk, calib=True, rnd=self.rnd)
            for site, v in z.items():
                acc[site] = v if site not in acc else jnp.maximum(acc[site], v)
        if not self.m["tied"]:
            ln_f, hw = self._head_w()
            _, acc["head"] = _head(x[:, -1], ln_f, hw, None, m=mk, calib=True,
                                   rnd=self.rnd)
        self.windows = {k: jnp.maximum(v, 1e-9) for k, v in acc.items()}
        return {k: np.asarray(v) for k, v in self.windows.items()}

    def served_logits(self, seqs: list[np.ndarray], starts: list[int],
                      max_len: int, rows: int, reduce) -> list:
        """For each sequence (prompt + served tokens), the logits at the
        ``rows`` positions from ``starts[i]`` on, passed to
        ``reduce(i, logits)`` as soon as they exist; returns its results.
        A sequence is padded to the least of a few fixed lengths
        (``pad_length``) that holds it, so that a cell compiles few shapes."""
        assert self.windows is not None, "calibrate first"
        mk = self.mkey
        xs, lens = [], []
        for s in seqs:
            t = np.zeros((pad_length(len(s), max_len),), np.int32)
            t[:len(s)] = s
            xs.append(self._embed(jnp.asarray(t))[None])
            lens.append(jnp.asarray([len(s)], jnp.int32))
        for i in range(self.m["layers"]):
            lw = self._layer_weights(i)
            for j in range(len(xs)):
                xs[j], _ = _layer(xs[j], lens[j], lw, self.windows, m=mk,
                                  calib=False, rnd=self.rnd)
        ln_f, hw = self._head_w()
        out = []
        for j, st in enumerate(starts):
            idx = np.minimum(np.arange(st, st + rows), xs[j].shape[1] - 1)
            logits, _ = _head(xs[j][0][idx], ln_f, hw, self.windows, m=mk,
                              calib=False, rnd=self.rnd)
            out.append(reduce(j, logits))
            xs[j] = None
        return out


def pad_length(n: int, max_len: int) -> int:
    """The padded length of an ``n``-token sequence: ``Q_BLOCK`` times a
    power of two, or the whole context ``max_len`` rounded up to a block."""
    whole = math.ceil(max(max_len, 1) / Q_BLOCK) * Q_BLOCK
    blocks = 1 << max(0, math.ceil(math.log2(max(n, 1) / Q_BLOCK)))
    return min(blocks * Q_BLOCK, whole)
