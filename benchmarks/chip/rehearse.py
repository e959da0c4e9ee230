#!/usr/bin/env python3
"""Compile one cell's round for a described TPU v5e, without a chip, and
print what the compiler reports: the bytes of ``memory_analysis`` and the
Pallas kernels (``tpu_custom_call``) in the compiled program.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py --workload <cell>

The engine is built on the CPU at the cell's real size, as a run builds it
(its parameters and banks live on the host), with the kernels forced on
(``fused_kernels`` true where the cell runs LBGM, no interpret mode); the
round is then lowered from those shapes, every argument placed on one
described chip, and compiled. Nothing runs, so it gives no time.
"""
import argparse
import json
import os
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
#: a Pallas call's instruction is named after its kernel: %<kernel>.<n> = ...
KERNEL_NAME = re.compile(r"^\s*(?:ROOT\s+)?%([A-Za-z_]\w*?)(?:\.\d+)? = ")


def rehearse(cell, topology: str = "v5e:2x2") -> dict:
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness
    from repro.kernels import ops
    if cell.chips != 1:
        raise SystemExit("rehearse.py compiles one-chip cells only")
    ops._default_interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology)
    one = SingleDeviceSharding(topo.devices[0])
    gen, fl, data, parts, _ = harness.inputs(cell, 0)
    kw = {"fused_kernels": True} if fl.get("use_lbgm", True) else {}
    engine = harness.build(cell, gen, fl, data, parts, **kw)
    rng = np.random.RandomState(0)
    batch = engine._sample_batches(rng)
    mask = np.asarray(engine._sample_mask(rng), np.float32)

    def shape(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            np.shape(x), x.dtype, sharding=one), tree)
    compiled = engine._round.lower(
        shape(engine.params), shape(engine.lbg), shape(engine.residual),
        shape(batch), shape(mask)).compile()
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "alias_size_in_bytes", "temp_size_in_bytes")
    out = {f: int(getattr(ma, f)) for f in fields}
    total = (out["argument_size_in_bytes"] + out["output_size_in_bytes"]
             - out["alias_size_in_bytes"] + out["temp_size_in_bytes"])
    return {"workload": cell.name, "topology": topology, **out,
            "total_gib": total / 2 ** 30,
            "tpu_custom_calls": len(calls),
            "kernels": sorted({m for ln in calls
                               for m in KERNEL_NAME.findall(ln)})}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    print(json.dumps(rehearse(harness.Cell(ROOT, args.workload))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
