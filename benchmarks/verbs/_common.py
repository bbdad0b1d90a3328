"""What the three verbs share: building the deployment from its
configuration file and holding the program's shapes to the file's."""

from __future__ import annotations

import dataclasses


def build(cell, record: bool = False):
    """``(app, cfg, fuzzer)`` through the builder the CLI verbs share."""
    from demi_tpu.parallel.distributed import build_workload

    app, cfg, fuzzer = build_workload(dict(cell.config["workload"]), record=record)
    have = dataclasses.asdict(cfg)
    for key, want in cell.config["shapes"].items():
        if have[key] != want:
            raise ValueError(
                f"{cell.config_name}: the program builds {key}={have[key]}, "
                f"the configuration file states {want}"
            )
    return app, cfg, fuzzer


def lane_mesh(devices):
    """The 4-chip cells' mesh over exactly the cell's devices; None on one."""
    if len(devices) <= 1:
        return None
    from demi_tpu.parallel.mesh import make_mesh

    return make_mesh(devices)


def host_config(app):
    from demi_tpu.apps.common import make_host_invariant
    from demi_tpu.config import SchedulerConfig

    return SchedulerConfig(invariant_check=make_host_invariant(app))


def build_native():
    """Both native libraries, in set-up: the racing scan and the record
    codec must not be built (or found missing) inside the window."""
    from demi_tpu.native.build import build_library, native_source

    for src, stem in (
        ("trace_analysis.cpp", "libdemi_analysis"),
        ("record_codec.cpp", "libdemi_records"),
    ):
        if build_library(native_source(src), stem) is None:
            raise RuntimeError(f"no compiler for native/{src}")
