"""What the benchmark knows of each model architecture, one package each.

A configuration file names its published class in ``architectures[0]``
(``Qwen2ForCausalLM``).  ``find`` scans the packages on this package's
``__path__`` for the one whose ``ARCHITECTURES`` holds that class.  Each
such package ``<name>/`` holds:

* ``program.py``: ``model_config(cfg)``, the program's ``ModelConfig`` for
  a configuration file (the benchmark's only hold on the program's
  configuration API);
* ``reference.py``: ``param_shapes(cfg)``, the parameter tree the program
  has to have, and ``Reference(cfg, seed, precision=None)`` with
  ``calibrate(tokens)`` and ``served_logits(...)``, the plain reference
  of the correctness check; it imports nothing of the program;
* ``work.py``: ``step_launches(cfg, m, head_rows)``, the TD-VMM launches of
  one step of ``m`` rows as ``(site, G, M, K, N, launches)`` (G: the tiles
  one launch computes, such as the experts of a batched expert launch),
  and ``model_work(cfg, tokens, heads, ranges)``, the model's
  ``(int8 ops, bf16 flops)`` for ``tokens`` processed positions, ``heads``
  rows through the head and the processed ``(start, end)`` position range
  of each request.

A configuration of another architecture comes in as a new package here;
nothing outside this directory names an architecture.
"""
from __future__ import annotations

import dataclasses
import importlib
import pkgutil
from types import ModuleType

PARTS = ("program", "reference", "work")


@dataclasses.dataclass(frozen=True)
class Arch:
    name: str
    program: ModuleType
    reference: ModuleType
    work: ModuleType


def find(cfg: dict, file: str | None = None) -> Arch:
    """The one architecture package that claims the configuration's class;
    none, or more than one, is an error that names the file."""
    cls = (cfg.get("architectures") or [None])[0]
    claims = []
    for info in pkgutil.iter_modules(__path__):
        if info.ispkg:
            pkg = importlib.import_module(f"{__name__}.{info.name}")
            if cls in getattr(pkg, "ARCHITECTURES", ()):
                claims.append(info.name)
    if len(claims) != 1:
        where = file or f"configuration {cfg.get('name')!r}"
        raise SystemExit(f"bench: {where}: architecture {cls!r} is claimed by "
                         f"{len(claims)} packages of bench/archs {claims}; "
                         "exactly one has to claim it")
    return Arch(claims[0], *(importlib.import_module(f"{__name__}.{claims[0]}.{part}")
                             for part in PARTS))
