#!/bin/sh
# One-shot TPU measurement sweep: every chip-only script in one call,
# each step its own process (one process holds the chip at a time) under
# a coreutils `timeout`, raw output saved under chiprun_out/measurements/
# (what the chip tool copies back).
# Run from the repo root through the chip tool:
#   chiprun --timeout 3600 -- sh scripts/run_tpu_measurements.sh
set -x
out=chiprun_out/measurements
mkdir -p "$out"
stamp=$(date +%Y%m%d-%H%M%S)

# 1. headline bench (the driver's metric): also records the storage tier
timeout 900 python bench.py 2>&1 | tee "$out/bench-$stamp.txt"

# 2. kernel decisions: storage tiers, 1-D vs 2-D resident SpMV layout,
#    ELL Pallas vs XLA gather, HBM-resident SpMV strategies
timeout 1800 python scripts/bench_kernels.py 2>&1 \
    | tee "$out/kernels-$stamp.txt"

# 3. milestone configs + the 100M-DOF north star (the 464^3 operator
#    build alone streams ~1.4 GB of bands; give it a generous budget)
timeout 1800 python scripts/bench_suite.py 2>&1 \
    | tee "$out/suite-$stamp.txt"
timeout 3600 python scripts/bench_suite.py --configs p3d-464-100M 2>&1 \
    | tee "$out/suite-100m-$stamp.txt"

# 4. full-scale correctness: 464^3 convergence with the residual
#    re-derived through the XLA path (independent of the Pallas kernel)
timeout 1800 python scripts/check_100m_convergence.py 2>&1 \
    | tee "$out/check100m-$stamp.txt"

# 5. the f32 fused-path A/B (see fused_plan_for): fused is the default
#    since 2026-07-31 (measured 25,578 vs 19,448 it/s); keep re-measuring
#    the question each sweep via the =0 escape hatch
timeout 900 python scripts/bench_suite.py --configs p3d-var-96 2>&1 \
    | tee "$out/var96-fusedf32-$stamp.txt"
ACG_TPU_FUSED_F32=0 timeout 900 python scripts/bench_suite.py \
    --configs p3d-var-96 2>&1 \
    | tee "$out/var96-xla-$stamp.txt"

# 5a. the FEM differential family: matrix -> tier routing -> solve at
#     >= 1M rows (suite-fem measurement family; expected tiers recorded
#     in PERF.md).  The 1M Delaunay build itself takes ~1 min.
timeout 2400 python scripts/bench_suite.py \
    --configs fem-1M,fem3d-200k,p3d-aniso-128 2>&1 \
    | tee "$out/suite-fem-$stamp.txt"

# 5b. fp64: the documented-deviation number (SURVEY §7) — the Pallas
#     tiers reject itemsize > 4, so f64 always takes the XLA path (see
#     chip_smoke.py's f64 line for what f64 keeps on this chip); record
#     the one number the deviation costs
timeout 900 python scripts/bench_suite.py --configs p3d-128 \
    --dtype float64 2>&1 | tee "$out/f64-p3d128-$stamp.txt"

# 6. per-op microbenchmarks (dev tool; confirms where the time goes)
timeout 900 python scripts/profile_cg.py 2>&1 \
    | tee "$out/profile-$stamp.txt"

# 6b. the pipelined-gap decomposition (VERDICT r4 item 3): isolation-time
#     every piece of the pipelined loop body + certify A/B + the pipe2d
#     single-kernel iteration
timeout 1200 python scripts/profile_pipelined.py 2>&1 \
    | tee "$out/profile-pipelined-$stamp.txt"
timeout 900 python scripts/bench_suite.py --configs p3d-128-pipe 2>&1 \
    | tee "$out/pipe128-$stamp.txt"

# 6c. the rand-512k experiment (VERDICT r4 item 9): auto vs forced-sgell
#     vs RCM+gather on uniform-random sparsity — beats 7.7 it/s or closes
#     the item with a measured bound
timeout 2400 python scripts/bench_rand512k.py 2>&1 \
    | tee "$out/rand512k-$stamp.txt"

# 7. device-initiated RDMA halo: Mosaic compile + loopback execution on
#    the real chip (the CPU interpreter cannot run remote DMA)
timeout 600 python scripts/check_rdma_tpu.py 2>&1 \
    | tee "$out/rdma-$stamp.txt"
