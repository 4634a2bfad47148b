"""Autotune TD-VMM kernel block sizes and regenerate autotune_table.py.

Sweeps (block_m, block_k, block_n) candidates per (M, K, N, dtype) launch
shape, times the fused Pallas path through ``ops.tdvmm_matmul`` (median of
repeats, ``block_until_ready``-fenced, early-abandoning candidates whose
first sample is already far off the best), and rewrites
``src/repro/kernels/tdvmm/autotune_table.py`` for the platform it ran on —
the other platform's table is preserved verbatim.

Shapes come from two sources:

  * a fixed list of reference shapes (always included, so the table keeps
    answering them), and
  * every launch shape the resolved plans emit
    (``configs.plan.plan_launch_shapes``) across the selected ``--archs``
    at ``--m`` tokens — the model-emitted work list.

Shapes whose FLOP count exceeds ``--measure-limit`` are not timed: on the
interpret platform the wall-clock model is known (time scales with the grid
*step count*, each step being a Python-level block dispatch), so the
largest-single-block candidate is written directly.  Pass a larger limit to
time them anyway.

Usage:
    python scripts/autotune_tdvmm.py                  # all archs, m=512
    python scripts/autotune_tdvmm.py --archs mamba2-1.3b qwen1.5-0.5b
    python scripts/autotune_tdvmm.py --dry-run        # print, don't write
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

TABLE_PATH = ROOT / "src" / "repro" / "kernels" / "tdvmm" / "autotune_table.py"

# Reference shapes, always swept so the table keeps its entries for them:
# model-width codes matmuls in every code dtype, the ragged grouped qkv and
# ssm.in_proj launches, and the perceptron case study.
BENCH_SHAPES: list[tuple[int, int, int, str]] = [
    # model-width codes matmuls in f32, int8 and int4 codes
    (512, 1024, 4096, "float32"),
    (512, 1024, 4096, "int8"),
    (512, 1024, 4096, "int4"),
    (256, 896, 896, "float32"),
    (33, 300, 130, "float32"),
    (512, 2048, 512, "float32"),
    (512, 2048, 512, "int8"),
    (512, 2048, 512, "int4"),
    # a full layer and the fused-epilogue shape
    (256, 1024, 4096, "int8"),
    (256, 1024, 512, "int8"),
    # ragged grouped concat launches (attn.qkv G=3, ssm.in_proj G=5)
    (64, 896, 1152, "int8"),
    (64, 512, 2432, "int8"),
    # the perceptron case-study shape
    (8, 128, 64, "float32"),
    (8, 128, 64, "int8"),
]

# Giant blocks: min(block, padded dim) clamps these to a single grid step in
# every dimension — the interpret-mode optimum whenever it fits in memory.
SINGLE_BLOCK = (1 << 14, 1 << 15, 1 << 15)


def _interpret_candidates(m, k, n, name):
    from repro.kernels.tdvmm import tdvmm
    cands = [
        SINGLE_BLOCK,                       # one grid step
        (SINGLE_BLOCK[0], SINGLE_BLOCK[1], 2048),  # walk N in big strides
        (512, SINGLE_BLOCK[1], 2048),
        tdvmm._heuristic_blocks(name, "interpret"),
    ]
    seen, out = set(), []
    for c in cands:
        if c not in seen:
            seen.add(c)
            out.append(c)
    return out


def _mosaic_candidates(m, k, n, name, vmem_bytes=14 * 2**20):
    """VMEM-budgeted MXU tiles: x block + w block (double-buffered streams)
    plus the f32 accumulator and output tile must fit the per-core budget."""
    from repro.kernels.tdvmm import tdvmm
    itemsize = 4 if name == "float32" else 1
    kdiv = 2 if name == "int4" else 1  # packed-unit K stream
    out = []
    for bm in (128, 256, 512):
        for bk in (512, 1024, 2048, 4096):
            for bn in (128, 256, 512):
                use = (2 * (bm * bk + bk * bn) * itemsize // kdiv
                       + 2 * bm * bn * 4)
                if use <= vmem_bytes:
                    out.append((bm, bk, bn))
    out.append(tdvmm._heuristic_blocks(name, "mosaic"))
    return sorted(set(out))


def _operands(m, k, n, name, rng):
    lim = 7 if name == "int4" else 63
    x = rng.integers(-lim, lim + 1, size=(m, k)).astype(np.int8)
    w = rng.integers(-lim, lim + 1, size=(k, n)).astype(np.int8)
    if name == "float32":
        x, w = x.astype(np.float32), w.astype(np.float32)
    xs = jnp.ones((m,), jnp.float32)
    ws = jnp.ones((n,), jnp.float32)
    return jnp.asarray(x), jnp.asarray(w), xs, ws


def _time_candidate(args_, blocks, code_dtype, interpret, best_us):
    """Median-of-repeats for one block candidate, early-abandoning when the
    first post-compile sample is already >= 2x the incumbent."""
    import functools

    from benchmarks.common import time_call
    from repro.kernels.tdvmm import ops

    x, w, xs, ws = args_
    fn = jax.jit(functools.partial(
        ops.tdvmm_matmul, gain=1e-4, out_bits=6, out_scale=0.5,
        backend="pallas", interpret=interpret, code_dtype=code_dtype,
        block_sizes=blocks))
    probe = time_call(fn, x, w, xs, ws, warmup=1, iters=1)
    if best_us is not None and probe >= 2.0 * best_us:
        return float(probe)
    return float(time_call(fn, x, w, xs, ws, warmup=0, iters=3))


def collect_shapes(arch_names, m):
    from repro.configs import archs, plan as planmod
    shapes = dict.fromkeys(BENCH_SHAPES)
    for a in arch_names:
        cfg = archs.get_config(a)
        for shp in planmod.plan_launch_shapes(cfg, m):
            shapes[shp] = None
    return list(shapes)


def sweep(shapes, measure_limit):
    from repro.kernels.tdvmm import tdvmm
    platform = tdvmm.autotune_platform()
    interpret = platform == "interpret"
    rng = np.random.default_rng(0)
    table, report = {}, []
    for m, k, n, name in shapes:
        key = (m, k, n, name)
        cands = (_interpret_candidates(m, k, n, name) if interpret
                 else _mosaic_candidates(m, k, n, name))
        if 2 * m * k * n > measure_limit:
            # Too big to time here: the interpret wall-clock model says
            # fewest grid steps wins, so take the single-block candidate.
            table[key] = cands[0]
            report.append((key, cands[0], None, "arithmetic"))
            continue
        best, best_us = None, None
        for cand in cands:
            code_dtype = {"float32": "f32"}.get(name, name)
            us = _time_candidate(
                (_operands(m, k, n, name, rng)), cand, code_dtype,
                interpret, best_us)
            if best_us is None or us < best_us:
                best, best_us = cand, us
        table[key] = best
        report.append((key, best, best_us, "measured"))
        print(f"  {m}x{k}x{n}:{name} -> {best}  ({best_us:.0f} us)")
    return platform, table, report


def render(platform, table):
    """Regenerate autotune_table.py: the swept platform's table is replaced,
    the other platform's entries are carried over verbatim."""
    from repro.kernels.tdvmm import autotune_table as current
    tables = {"mosaic": dict(current.MOSAIC_TABLE),
              "interpret": dict(current.INTERPRET_TABLE)}
    tables[platform] = table

    def fmt(tbl):
        lines = []
        for (m, k, n, name), blocks in sorted(tbl.items()):
            lines.append(f'    ({m}, {k}, {n}, "{name}"): {blocks!r},')
        return "\n".join(lines)

    doc = current.__doc__.rstrip("\n")
    return f'''"""{doc}
"""

# fmt: off
MOSAIC_TABLE: dict[tuple[int, int, int, str], tuple[int, int, int]] = {{
{fmt(tables["mosaic"])}
}}

INTERPRET_TABLE: dict[tuple[int, int, int, str], tuple[int, int, int]] = {{
{fmt(tables["interpret"])}
}}
# fmt: on
'''


def main(argv=None):
    from repro.configs import archs
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--archs", nargs="*", default=sorted(archs.ARCHS),
                    help="arch ids whose plan-emitted shapes to tune "
                         "(default: all)")
    ap.add_argument("--m", type=int, default=512,
                    help="token count M for plan-emitted shapes")
    ap.add_argument("--measure-limit", type=float, default=2e10,
                    help="max 2*M*K*N FLOPs to actually time; larger shapes "
                         "get the arithmetic single-block choice")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the table instead of writing it")
    args = ap.parse_args(argv)

    shapes = collect_shapes(args.archs, args.m)
    print(f"tuning {len(shapes)} shapes "
          f"({sum(1 for s in shapes if 2*s[0]*s[1]*s[2] <= args.measure_limit)}"
          f" measured)")
    platform, table, report = sweep(shapes, args.measure_limit)
    text = render(platform, table)
    if args.dry_run:
        print(text)
        return
    TABLE_PATH.write_text(text)
    measured = sum(1 for *_, how in report if how == "measured")
    print(f"wrote {TABLE_PATH} ({platform}: {len(table)} entries, "
          f"{measured} measured, {len(report) - measured} arithmetic)")


if __name__ == "__main__":
    main()
