"""The control for `correct`, and the faults it must catch.

The control is the program's own next precision down: the configuration
states bfloat16, and the program has an int8 path (`repro.quant`: int8
weights per output channel, int8 activations per tensor, int32
accumulation). `build_int8` serves the cell through it, on the same
engine, traffic and window as a run; the cell's limit has to fail it.

The faults wrap the program's executor underneath the wall clock:
`AlterTokens` changes every decoded token where it is produced,
`StaleState` returns the KV pool from a decode step unchanged, and
`HalfBatch` leaves half of each decode batch out. A run with any of them
has to come out not correct. (A cell on one chip has no exchange between
chips to leave out.)

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] [--program]

runs the control (or, with `--program`, the program itself) on each
seed in this one process, on the chip, and prints each seed's logit-gap
statistics (`run.GAP_STATS`), the readings a cell's limit is set from,
and `correct` as a run of the cell decides it with the committed limit.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from chipbench import arch, run as bench  # noqa: E402


def build_int8(cj: dict, seed: int):
    """The cell's engine, serving its weights through the int8 path. Each
    weight is quantized by `repro.quant.quantize_tree` in a jitted call of
    its own and its bfloat16 copy deleted, so that the device holds the
    bfloat16 weights and one leaf's temporaries at most."""
    import jax
    from repro.quant import INT8, quantize_tree
    params = arch.resolve(cj).program_params(cj, bench.weight_seed(seed))

    def visit(tree):
        if isinstance(tree, list):
            return [visit(v) for v in tree]
        out = {}
        for k, v in tree.items():
            if isinstance(v, (dict, list)):
                out[k] = visit(v)
                continue
            out[k] = jax.jit(lambda w, k=k: quantize_tree({k: w}, "int8")[k])(v)
            if all(x is not v for x in jax.tree.leaves(out[k])):
                v.delete()
        return out

    return bench.build(cj, seed, params=visit(params), qcfg=INT8)


class _Fault:
    def __init__(self, ex):
        self.ex = ex
        self.needs_tokens = True

    def reset_slot(self, slot):
        self.ex.reset_slot(slot)

    def prefill(self, *args):
        return self.ex.prefill(*args)


class AlterTokens(_Fault):
    """Every decoded token is replaced by its neighbour in the vocabulary."""

    def decode(self, tokens, active, block_tables, context_lens=None):
        nxt, dt = self.ex.decode(tokens, active, block_tables)
        vocab = self.ex.cfg.vocab_size
        return np.where(active, (nxt + 1) % vocab, nxt), dt


class StaleState(_Fault):
    """Each decode step hands back the KV pool it was given: the step's
    keys and values are never stored."""

    def decode(self, tokens, active, block_tables, context_lens=None):
        pools = self.ex.pools
        out = self.ex.decode(tokens, active, block_tables)
        self.ex.pools = pools
        return out


class HalfBatch(_Fault):
    """Each decode step computes the first half of its active rows; the
    rest are left out and handed back their input token."""

    def decode(self, tokens, active, block_tables, context_lens=None):
        rows = np.flatnonzero(active)
        kept = np.array(active)
        kept[rows[len(rows) // 2:]] = False
        nxt, dt = self.ex.decode(tokens, kept, block_tables)
        return np.where(kept, nxt, tokens), dt


FAULTS = {"alter_tokens": AlterTokens, "stale_state": StaleState,
          "half_batch": HalfBatch}


def readings(cell_name: str, seconds: float, seeds, program: bool,
             log=print) -> list:
    """Every logit-gap statistic of each seed's run, on the chip, in one
    process."""
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench_json, cell, config, mix = bench.load_cell(cell_name)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise bench.NoChip(f"needs a TPU; found {dev.platform!r}")
    peaks = bench.peaks_for(dev.device_kind)
    out = []
    for seed in seeds:
        t0 = time.perf_counter()
        served = bench.serve(cell, config, mix, peaks, seed=seed,
                             seconds=seconds, trace=False,
                             make_engine=bench.build if program
                             else build_int8, log=log)
        checks, stats = bench.check(served, config, bench.load_limits(cell),
                                    seed, log=log)
        correct = all(c["ok"] for c in checks.values())
        log(f"{'program' if program else 'int8 control'} {cell_name} seed "
            f"{seed}: correct {correct}, {stats} in "
            f"{time.perf_counter() - t0:.1f}s")
        out.append({"seed": seed, "correct": correct, **stats,
                    "usd_per_mtok": bench.reader("usd_per_mtok")(served.run)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true",
                    help="read the program, not the int8 control")
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        res = readings(args.workload, args.seconds, args.seeds,
                       args.program, log=log)
    except bench.NoChip as e:
        log(f"chipbench control: {e}")
        return 3
    print(json.dumps({"workload": args.workload,
                      "path": "program" if args.program else "int8",
                      "readings": res}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
