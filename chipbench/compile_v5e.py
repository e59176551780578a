"""Compile a configuration's served decode step and its longest prefill
for a described TPU v5e, with no chip, and print what the compiler says
they hold in memory.

    JAX_PLATFORMS=cpu python3 chipbench/compile_v5e.py \
        chipbench/configs/<config>.json <longest prefill length>

The step functions are the program's own (`serving/runner.py`), built
with the Pallas paged kernel as a TPU serves them; the arguments are
shapes only, the served weights' as the configuration's architecture
(`chipbench/arch.py`) makes them.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(config_path: str, prefill_len: int) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import arch
    from chipbench.run import model_config
    from repro.serving.runner import init_pools, make_step_fns

    cj = json.loads(Path(config_path).read_text())
    cfg = model_config(cj)
    e = cj["engine"]
    B, P, page = e["max_batch"], e["max_pages_per_seq"], e["page_size"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    params = on_chip(jax.eval_shape(
        lambda: arch.resolve(cj).program_params(cj, 0)))
    pools = on_chip(jax.eval_shape(
        lambda: init_pools(cfg, B * P + 1, page, B)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa: E731
    b1 = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one)
    prefill_fn, decode_fn = make_step_fns(cfg, page, use_kernel=True)
    calls = {
        "decode": (decode_fn, (params, pools, i32(B, P), i32(B), i32(B), b1)),
        f"prefill L={prefill_len}": (prefill_fn, (
            params, pools, i32(B, P), i32(B), i32(B, prefill_len), i32(B),
            b1)),
    }
    for name, (fn, args) in calls.items():
        t0 = time.perf_counter()
        compiled = fn.lower(*args).compile()
        secs = time.perf_counter() - t0
        m = compiled.memory_analysis()
        gb = 1e9
        print(f"{cj['name']} {name}: compiled in {secs:.1f} s; arguments "
              f"{m.argument_size_in_bytes / gb:.3f} GB, outputs "
              f"{m.output_size_in_bytes / gb:.3f} GB, temp "
              f"{m.temp_size_in_bytes / gb:.3f} GB, aliased "
              f"{m.alias_size_in_bytes / gb:.3f} GB; kernel in program: "
              f"{'tpu_custom_call' in compiled.as_text()}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2])))
