"""Plain reference of an AFMoE model (Arcee Trinity), in jax.numpy.

It imports nothing of the program.  From the benchmark it takes the seeded
weights (``weights.draw``) and the configuration file; it calibrates its own
readout windows on the seeded calibration batch, as the program's
``calibrate`` pass does, and runs each sequence whole: no cache, no paging,
no chunking, no batching across requests, no kernel.

The model, as recalled of the public ``modeling_afmoe.py`` (nothing could be
fetched to re-check it)::

    x = embed(tokens) * sqrt(d)                                  # mup_enabled
    per layer:
      a = RMSNorm_in(x)
      q = RMSNorm_q(a Wq), k = RMSNorm_k(a Wk)   per head;   v = a Wv
      window layer: q, k = RoPE(q, k);  keys j with p - W < j <= p
      full layer:   no RoPE;            keys j <= p
      o = softmax(q k^T / sqrt(head_dim)) v    (grouped KV heads)
      o = o * sigmoid(a Wg)
      x = x + RMSNorm_post_attn(o Wo)
      b = RMSNorm_pre_mlp(x)
      f = SwiGLU(b) on the leading dense layers, else MoE(b)
      x = x + RMSNorm_post_mlp(f)
    logits = RMSNorm_f(x) W_head
    MoE(b): s = sigmoid(b W_r) in float32 over all E experts
            sel = top_k(s + expert_bias);  w = s[sel] / (sum s[sel] + 1e-20) * route_scale
            MoE(b) = SwiGLU_shared(b) + sum_{e in sel} w_e SwiGLU_e(b)

Where it departs from the published model, as the program does:

* The expert share: the layer holds ``num_experts`` of the published
  ``published.num_experts`` experts, from ``expert_offset``.  It routes over
  all of them and sums only its held experts' part; what the experts held
  elsewhere would add is left out.  Computed densely here: every held
  expert on every row, weighted by its routing weight (0 where not chosen).
* Each analog site is the time-domain multiplier of arXiv:1711.10673 in
  closed form, as in ``archs/qwen2/reference.py``: ``bits``-bit row codes,
  ``weight_bits``-bit column codes, an exact integer charge sum, the latch
  normalisation, a ``bits``-bit readout over the site's calibrated window.
  ``attn.qkv`` covers Wq, Wk, Wv and Wg (one window each); each expert of
  ``moe.expert.in`` / ``.out`` is its own tile with its own window, over
  the rows routed to it (an expert that calibration routes no row to takes
  the largest window of its site); the router stays digital.
* Values are held in the configuration's precision (``torch_dtype``,
  bfloat16) wherever the program keeps them: weights but the router's,
  the residual stream, norm outputs, site outputs, q/k/v, scores,
  probabilities, attention and gate outputs, expert outputs, logits.
  Everything between (norms, rotary angles, softmax, routing, the weighted
  sum of the experts, every matmul's accumulation) runs in float32,
  matmuls at the highest precision.  ``precision="fp8"`` is the control:
  those values held in float8 e4m3, the step below bfloat16;
  ``precision="float32"`` holds nothing below float32 (the CPU tests'
  comparison with the program run in float32).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as wts

Q_BLOCK = 512
ROUTER = ("moe/router/w", "moe/router/b")   # held in float32


def dims(cfg: dict) -> dict:
    """Shapes the reference needs, from the configuration file's keys."""
    pad = cfg["vocab_pad_multiple"]
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
            "layers": cfg["num_hidden_layers"], "dense": cfg["num_dense_layers"],
            "experts": cfg["published"]["num_experts"],
            "held": cfg["num_experts"], "offset": cfg["expert_offset"],
            "top_k": cfg["num_experts_per_tok"],
            "shared": cfg["num_shared_experts"],
            "scale": float(cfg["route_scale"]), "window": cfg["sliding_window"],
            "vocab": cfg["vocab_size"], "vp": -(-cfg["vocab_size"] // pad) * pad,
            "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
            "bits": cfg["tdvmm"]["bits"], "wbits": cfg["tdvmm"]["weight_bits"]}


def segments(cfg: dict) -> list[tuple[bool, bool, int]]:
    """(dense, sliding, layers) of each run of layers of one kind, in
    order: the program stacks each run's weights as one segment."""
    m = dims(cfg)
    runs: list[list] = []
    for layer, kind in enumerate(cfg["layer_types"][:m["layers"]]):
        key = [layer < m["dense"], kind == "sliding_attention"]
        if runs and runs[-1][:2] == key:
            runs[-1][2] += 1
        else:
            runs.append(key + [1])
    return [tuple(r) for r in runs]


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Path -> shape of every weight, in the program's tree layout (the
    harness checks the program's tree against this before a run)."""
    m = dims(cfg)
    d, q, kv, hd = m["d"], m["h"] * m["hd"], m["kv"] * m["hd"], m["hd"]
    out = {"embed/table": (m["vp"], d), "ln_f/scale": (d,), "head/w": (d, m["vp"])}
    for i, (dense, _, n) in enumerate(segments(cfg)):
        blk = f"blocks/seg{i}/"
        for norm in ("ln1", "ln2", "ln_post_attn", "ln_post_ffn"):
            out[blk + norm + "/scale"] = (n, d)
        out.update({blk + "attn/wq/w": (n, d, q), blk + "attn/wk/w": (n, d, kv),
                    blk + "attn/wv/w": (n, d, kv), blk + "attn/wg/w": (n, d, q),
                    blk + "attn/wo/w": (n, q, d),
                    blk + "attn/q_norm/scale": (n, hd),
                    blk + "attn/k_norm/scale": (n, hd)})
        if dense:
            out.update({blk + "ffn/w_gate/w": (n, d, m["f"]),
                        blk + "ffn/w_up/w": (n, d, m["f"]),
                        blk + "ffn/w_down/w": (n, m["f"], d)})
            continue
        out.update({blk + ROUTER[0]: (n, d, m["experts"]),
                    blk + ROUTER[1]: (n, m["experts"])})
        for bank, e in (("experts", m["held"]), ("shared", m["shared"])):
            out.update({blk + f"moe/{bank}/w_gate": (n, e, d, m["fe"]),
                        blk + f"moe/{bank}/w_up": (n, e, d, m["fe"]),
                        blk + f"moe/{bank}/w_down": (n, e, m["fe"], d)})
    return out


def _held(dtype):
    """Round a float32 value to where the configuration holds it."""
    def rnd(x):
        return x.astype(dtype).astype(jnp.float32)
    rnd.__name__ = f"held_{jnp.dtype(dtype).name}"
    return rnd


PRECISIONS = {"bfloat16": _held(jnp.bfloat16), "fp8": _held(jnp.float8_e4m3fn),
              "float32": lambda x: x}


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


def _site(x, w, window, bits, wbits, rows=None):
    """The analog multiplier on x (M, K) and w (K, N).  ``window=None``
    calibrates the readout on this call's own max|z| over the rows where
    ``rows`` holds (all rows by default).  Returns (y, that max|z|)."""
    lx, lw = (1 << bits) - 1, (1 << wbits) - 1
    k = x.shape[-1]
    sx = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-6)
    xc = jnp.round(jnp.clip(x / sx, -1.0, 1.0) * lx).astype(jnp.int8)
    wm = jnp.maximum(jnp.max(jnp.abs(w), 0, keepdims=True), 1e-6)
    wc = jnp.round(jnp.clip(w / wm, -1.0, 1.0) * lw).astype(jnp.int8)
    acc = jnp.dot(xc, wc, preferred_element_type=jnp.int32)
    z = acc.astype(jnp.float32) * jnp.float32(1.0 / (lx * lw * 2.0 * k))
    az = jnp.abs(z) if rows is None else jnp.where(rows[:, None], jnp.abs(z), 0.0)
    zmax = jnp.max(az)
    win = jnp.maximum(zmax, 1e-9) if window is None else window
    q = jnp.round(jnp.clip(z / win, -1.0, 1.0) * lx)
    return q * (win / lx) * (2.0 * k) * sx * wm, zmax


def _attend(q, k, v, length, window, rnd):
    """Causal attention of one sequence, over the ``window`` latest keys
    where ``window`` is set.  q: (S, H, D), k/v: (S, KV, D); keys at or past
    ``length`` (padding) are masked; query blocks of ``Q_BLOCK``."""
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
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        p = rnd(jax.nn.softmax(jnp.where(mask[None], sc, -1e30), -1))
        return rnd(jnp.einsum("hqk,khd->qhd", p, vv))

    out = jax.lax.map(block, jnp.arange(s // bq))
    return out.reshape(s, h, dh)


def _swiglu(site, b, wg, wu, wd, win_in, win_out, rnd, rows=None):
    """SwiGLU through three analog launches: (out, max|z| in, max|z| out)."""
    gate, z_g = site(b, wg, win_in, rows=rows)
    up, z_u = site(b, wu, win_in, rows=rows)
    gate = rnd(gate)
    act = rnd(rnd(gate * rnd(jax.nn.sigmoid(gate))) * rnd(up))
    dn, z_d = site(act, wd, win_out, rows=rows)
    return rnd(dn), jnp.maximum(z_g, z_u), z_d


def _moe(site, b, lw, win, m, rnd):
    """The held experts' part plus the shared expert, on rows b (T, d).
    Returns (f, the sites' max|z|)."""
    logits = jnp.dot(b, lw["router_w"])                      # float32
    s = jax.nn.sigmoid(logits)
    _, sel = jax.lax.top_k(s + lw["router_b"], m["top_k"])
    ws = jnp.take_along_axis(s, sel, -1)
    w = ws / (ws.sum(-1, keepdims=True) + 1e-20) * m["scale"]
    ids = m["offset"] + jnp.arange(m["held"])
    chosen = sel[:, :, None] == ids[None, None, :]            # (T, K, held)
    weight = jnp.sum(jnp.where(chosen, w[:, :, None], 0.0), 1)   # (T, held)
    routed = jnp.any(chosen, 1)

    def expert(acc, e):
        wg, wu, wd, w_in, w_out, we, re = e
        out, z_in, z_out = _swiglu(site, b, rnd(wg.astype(jnp.float32)),
                                   rnd(wu.astype(jnp.float32)),
                                   rnd(wd.astype(jnp.float32)), w_in, w_out,
                                   rnd, rows=re)
        return acc + we[:, None] * out, (z_in, z_out)

    xs = (lw["e_gate"], lw["e_up"], lw["e_down"],
          None if win is None else win["moe.expert.in"],
          None if win is None else win["moe.expert.out"], weight.T, routed.T)
    acc, (z_in, z_out) = jax.lax.scan(expert, jnp.zeros_like(b), xs)
    sh, zs_in, zs_out = _swiglu(
        site, b, *(rnd(lw[n][0].astype(jnp.float32)) for n in ("s_gate", "s_up", "s_down")),
        None if win is None else win["moe.shared.in"],
        None if win is None else win["moe.shared.out"], rnd)
    f = rnd(rnd(acc) + sh)
    return f, {"moe.expert.in": z_in, "moe.expert.out": z_out,
               "moe.shared.in": zs_in, "moe.shared.out": zs_out}


@functools.partial(jax.jit, static_argnames=("m", "dense", "sliding", "calib", "rnd"))
def _layer(x, lens, lw, win, *, m, dense, sliding, calib, rnd):
    """One layer over rows of x (R, S, d).  ``calib``: readout windows come
    from each call's own data (the calibration pass).  Returns (x, zmax)."""
    m = dict(m)
    lw = {k: v if k in ("router_w", "router_b") or k.startswith(("e_", "s_"))
          else rnd(v.astype(jnp.float32)) for k, v in lw.items()}
    win = None if calib else win
    r, s, d = x.shape
    site = functools.partial(_site, bits=m["bits"], wbits=m["wbits"])

    def w_(name):
        return None if win is None else win[name]

    a = rnd(_rmsnorm(x, lw["ln1"], m["eps"])).reshape(r * s, d)
    proj, z_qkv = [], []
    for j, name in enumerate(("wq", "wk", "wv", "wg")):
        y, z = site(a, lw[name], None if win is None else win["attn.qkv"][j])
        proj.append(rnd(y))
        z_qkv.append(z)
    q = proj[0].reshape(r, s, m["h"], m["hd"])
    k = proj[1].reshape(r, s, m["kv"], m["hd"])
    v = proj[2].reshape(r, s, m["kv"], m["hd"])
    gate = proj[3].reshape(r, s, m["h"], m["hd"])
    q = rnd(_rmsnorm(q, lw["q_norm"], m["eps"]))
    k = rnd(_rmsnorm(k, lw["k_norm"], m["eps"]))
    if sliding:
        pos = jnp.arange(s)
        rope = jax.vmap(lambda t: rnd(_rope(t, pos, m["theta"])))
        q, k = rope(q), rope(k)
    window = m["window"] if sliding else None
    att = jax.vmap(lambda a_, b_, c_, n: _attend(a_, b_, c_, n, window, rnd))(
        q, k, v, lens)
    att = rnd(att * rnd(jax.nn.sigmoid(gate)))
    o, z_out = site(att.reshape(r * s, -1), lw["wo"], w_("attn.out"))
    x = rnd(x + rnd(_rmsnorm(rnd(o), lw["ln_post_attn"], m["eps"])).reshape(r, s, d))
    b = rnd(_rmsnorm(x, lw["ln2"], m["eps"])).reshape(r * s, d)
    zmax = {"attn.qkv": jnp.stack(z_qkv), "attn.out": z_out}
    if dense:
        f, zmax["ffn.in"], zmax["ffn.out"] = _swiglu(
            site, b, lw["w_gate"], lw["w_up"], lw["w_down"], w_("ffn.in"),
            w_("ffn.out"), rnd)
    else:
        f, z = _moe(site, b, lw, win, m, rnd)
        zmax.update(z)
    x = rnd(x + rnd(_rmsnorm(f, lw["ln_post_ffn"], m["eps"])).reshape(r, s, d))
    return x, zmax


@functools.partial(jax.jit, static_argnames=("m", "calib", "rnd"))
def _head(x, ln_f, hw, win, *, m, calib, rnd):
    """Final norm and the analog head on rows x (N, d): logits (N, vocab),
    max|z|."""
    m = dict(m)
    ln_f, hw = (rnd(a.astype(jnp.float32)) for a in (ln_f, hw))
    h = rnd(_rmsnorm(x, ln_f, m["eps"]))
    logits, z = _site(h, hw, None if calib else win["head"], m["bits"], m["wbits"])
    return rnd(logits)[:, :m["vocab"]], z


LAYER_NAMES = {"ln1": "ln1/scale", "ln2": "ln2/scale",
               "ln_post_attn": "ln_post_attn/scale",
               "ln_post_ffn": "ln_post_ffn/scale",
               "wq": "attn/wq/w", "wk": "attn/wk/w", "wv": "attn/wv/w",
               "wg": "attn/wg/w", "wo": "attn/wo/w",
               "q_norm": "attn/q_norm/scale", "k_norm": "attn/k_norm/scale"}
DENSE_NAMES = {"w_gate": "ffn/w_gate/w", "w_up": "ffn/w_up/w",
               "w_down": "ffn/w_down/w"}
MOE_NAMES = {"router_w": ROUTER[0], "router_b": ROUTER[1],
             "e_gate": "moe/experts/w_gate", "e_up": "moe/experts/w_up",
             "e_down": "moe/experts/w_down", "s_gate": "moe/shared/w_gate",
             "s_up": "moe/shared/w_up", "s_down": "moe/shared/w_down"}


class Reference:
    """The reference model for one configuration and seed.  Weights are
    drawn on the device as the configuration states them (bfloat16; the
    router float32) and held there until the object is dropped."""

    def __init__(self, cfg: dict, seed: int, precision: str | None = None):
        self.m = dims(cfg)
        self.mkey = tuple(sorted(self.m.items()))
        self.rnd = PRECISIONS[precision or cfg["torch_dtype"]]
        key = wts.base_key(seed)
        draw = jax.jit(wts.draw, static_argnums=(1, 2, 3))
        self.w = {p: draw(key, p, s, jnp.float32 if p.endswith(ROUTER) else jnp.bfloat16)
                  for p, s in param_shapes(cfg).items()}
        self.layers = []          # (segment, index in it, dense, sliding)
        for i, (dense, sliding, n) in enumerate(segments(cfg)):
            self.layers += [(i, j, dense, sliding) for j in range(n)]
        self.windows = None

    def _layer_weights(self, layer: int) -> dict:
        seg, j, dense, _ = self.layers[layer]
        names = {**LAYER_NAMES, **(DENSE_NAMES if dense else MOE_NAMES)}
        return {k: self.w[f"blocks/seg{seg}/{p}"][j] for k, p in names.items()}

    def _run_layer(self, layer, x, lens, win, calib):
        _, _, dense, sliding = self.layers[layer]
        return _layer(x, lens, self._layer_weights(layer), win, m=self.mkey,
                      dense=dense, sliding=sliding, calib=calib, rnd=self.rnd)

    def _embed(self, tokens):
        table = self.w["embed/table"][tokens].astype(jnp.float32)
        return self.rnd(table * math.sqrt(self.m["d"]))

    def calibrate(self, tokens: np.ndarray) -> dict:
        """Readout windows from the calibration batch (rows, length): the
        max|z| of each site over every layer and row (of each expert, over
        the rows routed to it); the head's over each row's last position,
        as the program's calibration pass takes them."""
        x = self._embed(jnp.asarray(tokens))
        lens = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
        acc: dict = {}
        for i in range(len(self.layers)):
            x, z = self._run_layer(i, x, lens, None, True)
            for site, v in z.items():
                acc[site] = v if site not in acc else jnp.maximum(acc[site], v)
        _, acc["head"] = _head(x[:, -1], self.w["ln_f/scale"], self.w["head/w"],
                               None, m=self.mkey, calib=True, rnd=self.rnd)
        for site in ("moe.expert.in", "moe.expert.out"):
            v = acc[site]
            acc[site] = jnp.where(v == 0.0, jnp.max(v), v)
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
        xs, lens = [], []
        for s in seqs:
            t = np.zeros((pad_length(len(s), max_len),), np.int32)
            t[:len(s)] = s
            xs.append(self._embed(jnp.asarray(t))[None])
            lens.append(jnp.asarray([len(s)], jnp.int32))
        for i in range(len(self.layers)):
            for j in range(len(xs)):
                xs[j], _ = self._run_layer(i, xs[j], lens[j], self.windows, False)
        out = []
        for j, st in enumerate(starts):
            idx = np.minimum(np.arange(st, st + rows), xs[j].shape[1] - 1)
            logits, _ = _head(xs[j][0][idx], self.w["ln_f/scale"], self.w["head/w"],
                              self.windows, m=self.mkey, calib=False, rnd=self.rnd)
            out.append(reduce(j, logits))
            xs[j] = None
        return out


def pad_length(n: int, max_len: int) -> int:
    """The padded length of an ``n``-token sequence: ``Q_BLOCK`` times a
    power of two, or the whole context ``max_len`` rounded up to a block."""
    whole = math.ceil(max(max_len, 1) / Q_BLOCK) * Q_BLOCK
    blocks = 1 << max(0, math.ceil(math.log2(max(n, 1) / Q_BLOCK)))
    return min(blocks * Q_BLOCK, whole)
