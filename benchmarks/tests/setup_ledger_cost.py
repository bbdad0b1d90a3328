#!/usr/bin/env python3
"""What the set-up ledger costs a call, on this host (PR 36): the compile
listener pair fed synthetic events, a stage, ``first_job`` on a later
job, and a dead span beside them.

    chiprun -- python3 benchmarks/tests/setup_ledger_cost.py

Not a test."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import demi_tpu.device  # noqa: E402,F401
from demi_tpu import obs  # noqa: E402
from demi_tpu.obs import spans  # noqa: E402

N = 200_000
t0 = time.perf_counter()
for i in range(N):
    spans._on_duration(spans._TRACE_EVENT, 1e-6, fun_name="f")
a = (time.perf_counter() - t0) / N
t0 = time.perf_counter()
for i in range(N):
    spans._on_duration("/jax/other", 1e-6, fun_name="f")
b = (time.perf_counter() - t0) / N
t0 = time.perf_counter()
for i in range(N):
    spans._on_event("/jax/other")
c = (time.perf_counter() - t0) / N
t0 = time.perf_counter()
for i in range(N):
    with spans.stage("setup.build", what="x"):
        pass
d = (time.perf_counter() - t0) / N
t0 = time.perf_counter()
for i in range(N):
    with spans.first_job(2, "sweep"):
        pass
e = (time.perf_counter() - t0) / N
t0 = time.perf_counter()
for i in range(N):
    with obs.span("dead"):
        pass
f = (time.perf_counter() - t0) / N
print(f"[micro] listener compile event {a*1e6:.3f} us, other duration event {b*1e6:.3f} us, "
      f"other event {c*1e6:.3f} us, stage {d*1e6:.3f} us, first_job(job 2) {e*1e6:.3f} us, dead span {f*1e6:.3f} us")
