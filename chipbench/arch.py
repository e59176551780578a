"""Which code makes a configuration's served weights, its plain reference
and its counts.

A configuration file may name its architecture: `"arch": "<name>"` is the
module `chipbench/archs/<name>.py`, which defines

    program_params(cj, seed)    the parameter tree the program serves,
                                made on the device from the seed
    served_gaps(cj, seed, seqs, prompt_lens, length)
                                the float32 reference's logit gap of
                                every served token (`reference.py`)
    shape(cj)                   the counts the metric readers take
                                (`flops.Shape`'s methods)

A file without `"arch"` is the pre-norm decoder of `weights.py`,
`reference.py` and `flops.py`: GQA attention, then a SwiGLU MLP or a
top-k mixture of SwiGLU experts. So a new architecture is new files only:
its configuration, its module here, its limits, traffic and readers.
"""
from __future__ import annotations

import functools
import importlib.util
import re
import types
from pathlib import Path

from chipbench import flops, reference, weights

ARCHS_DIR = Path(__file__).resolve().parent / "archs"
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
PARTS = ("program_params", "served_gaps", "shape")

DEFAULT = types.SimpleNamespace(program_params=weights.program_params,
                                served_gaps=reference.served_gaps,
                                shape=flops.Shape.of)


@functools.lru_cache(maxsize=None)
def _load(path: Path):
    """One module object a file, so its jitted functions compile once."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_arch_{path.stem.replace('-', '_').replace('.', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [p for p in PARTS if not callable(getattr(mod, p, None))]
    if missing:
        raise ValueError(f"{path.name} defines no {', '.join(missing)}")
    return mod


def resolve(cj: dict):
    """The module (or `DEFAULT`) that computes configuration `cj`."""
    name = cj.get("arch")
    if name is None:
        return DEFAULT
    if not NAME.fullmatch(name):
        raise ValueError(f"arch {name!r} is not a name")
    path = ARCHS_DIR / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"arch {name!r}: no {path}")
    return _load(path)
