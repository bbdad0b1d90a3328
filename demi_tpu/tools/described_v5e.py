"""A sweep segment compiled for a described v5e: no chip, about ten seconds.

``python -m demi_tpu.tools.described_v5e raft5-multivote --lanes 8192``
builds the configuration's continuous-sweep segment
(``continuous.make_segment_kernel``, ``index_mode='onehot'``: what the chip
runs), compiles it with the TPU's compiler for one chip of a ``v5e:2x2``
that is described and not attached, and prints what the compiler decided:
the module's fusions and copies ranked by the compiler's own
``estimated_cycles``, each with its output's shape and layout, their sum,
and the ops that carry the lane batch anywhere but on the minor axis (a
``[lanes, 5, 7]`` in ``{2,1,0}`` puts 5 actors on the 128 lanes: PERF.md,
PR 50). ``--text`` writes the compiled module there.

Nothing runs: the cycles are the compiler's model and rank forms of one
program, they are not times (a time comes from the chip). The names are
the ones a profile of the chip shows, so a ledger's ``breakdown.device_ops``
can be read against this list.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
from typing import Optional

_CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "benchmarks", "configs",
)
# `%name = type[dims]{layout} opcode(` of one HLO instruction; a tuple-typed
# one (a multi-output fusion) opens with `(`, and its first member speaks
# for it.
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?(?P<name>[\w.\-]+) = \(?(?P<dtype>\w+)\[(?P<dims>[\d,]*)\]"
    r"(?:\{(?P<layout>[\d,]*)[^}]*\})?.*? (?P<opcode>[\w\-]+)\("
)
_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')


def load_workload(configuration: str) -> dict:
    """The ``workload`` of a benchmark configuration: a name under
    ``benchmarks/configs`` or a path to such a file."""
    path = configuration
    if not os.path.exists(path):
        path = os.path.join(_CONFIGS, configuration + ".json")
    with open(path, encoding="utf-8") as f:
        return dict(json.load(f)["workload"])


def compile_segment(workload: dict, lanes: int, seg_steps: Optional[int] = None):
    """The segment of ``workload`` at ``lanes``, compiled for one described
    v5e chip: ``(compiled, seg_steps)``. Raises what the chip's compiler
    would raise, and ``RuntimeError`` where no v5e can be described."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from ..device import continuous
    from ..device.encoding import empty_programs
    from ..parallel.distributed import build_workload
    from ..parallel.sweep import segment_steps

    app, cfg, _fuzzer = build_workload(workload)
    cfg = dataclasses.replace(cfg, index_mode="onehot")
    seg_steps = seg_steps or segment_steps(cfg.max_steps)
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # libtpu missing, or held by another process
        raise RuntimeError(f"no v5e:2x2 can be described here: {e}") from e
    one_chip = SingleDeviceSharding(topo.devices[0])

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    keys = jax.ShapeDtypeStruct((lanes, 2), jnp.uint32)
    state = jax.eval_shape(
        jax.vmap(lambda key: continuous.init_state(app, cfg, key)), keys
    )
    args = jax.tree_util.tree_map(
        described,
        (state, empty_programs(cfg, lanes),
         jax.ShapeDtypeStruct((lanes,), jnp.int32)),
    )
    seg = jax.vmap(continuous._segment_lane_fn(app, cfg, seg_steps))
    return jax.jit(seg).lower(*args).compile(), seg_steps


def module_ops(text: str) -> list:
    """Every fusion and copy of a compiled module's text that carries an
    ``estimated_cycles``: name, opcode, dtype, dims, layout (minor to
    major), cycles; the costliest first."""
    ops = []
    for line in text.splitlines():
        cycles = _CYCLES.search(line)
        found = _INSTRUCTION.match(line)
        if cycles is None or found is None:
            continue
        ops.append({
            "name": found["name"],
            "opcode": found["opcode"],
            "dtype": found["dtype"],
            "dims": [int(d) for d in found["dims"].split(",") if d],
            "layout": [int(d) for d in (found["layout"] or "").split(",") if d],
            "cycles": int(cycles[1]),
        })
    ops.sort(key=lambda op: -op["cycles"])
    return ops


def not_batch_minor(op: dict, lanes: int) -> bool:
    """Whether ``op`` carries the lane batch (a leading axis of ``lanes``)
    anywhere but on the minor axis."""
    dims, layout = op["dims"], op["layout"]
    return len(dims) > 1 and dims[0] == lanes and bool(layout) and layout[0] != 0


def tile_fill(op: dict) -> float:
    """The share of the ``(8, 128)`` tiles of ``op``'s two minor axes that
    its elements fill: 25 of 1,024 for 5 actors by 5 rows."""
    extents = [op["dims"][axis] for axis in op["layout"][:2]]
    fill = 1.0
    for extent, tile in zip(extents, (128, 8)):
        fill *= extent / (-(-extent // tile) * tile)
    return round(fill, 4)


def _row(op: dict) -> dict:
    dims = ",".join(map(str, op["dims"]))
    layout = ",".join(map(str, op["layout"]))
    return {
        "name": op["name"], "cycles": op["cycles"],
        "shape": f"{op['dtype']}[{dims}]{{{layout}}}",
        "tile_fill": tile_fill(op),
    }


def report(text: str, lanes: int, top: int) -> dict:
    """What ``main`` prints of a compiled module's text."""
    ops = module_ops(text)
    return {
        "estimated_cycles": sum(op["cycles"] for op in ops),
        "ops": len(ops),
        "dot_generals": len(re.findall(r"\bconvolution\(|\bdot\(", text)),
        "top": [_row(op) for op in ops[:top]],
        "not_batch_minor": [
            _row(op) for op in ops if not_batch_minor(op, lanes)
        ],
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "configuration",
        help="a name under benchmarks/configs, or a configuration file",
    )
    parser.add_argument("--lanes", type=int, required=True)
    parser.add_argument(
        "--seg-steps", type=int, default=None,
        help="default: the sweep driver's (parallel/sweep.segment_steps)",
    )
    parser.add_argument("--top", type=int, default=16)
    parser.add_argument("--text", help="write the compiled module's text here")
    args = parser.parse_args(argv)

    compiled, seg_steps = compile_segment(
        load_workload(args.configuration), args.lanes, args.seg_steps
    )
    text = compiled.as_text()
    if args.text:
        with open(args.text, "w", encoding="utf-8") as f:
            f.write(text)
    out = report(text, args.lanes, args.top)
    out = {
        "configuration": args.configuration, "lanes": args.lanes,
        "seg_steps": seg_steps, **out,
    }
    json.dump(out, sys.stdout, indent=1)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
