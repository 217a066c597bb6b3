#!/usr/bin/env python
"""NVMe-tier host-side microbench: DiskChunkStore traffic at real chunk sizes.

The ZeRO-Infinity-style disk tier (``offload_optimizer_device="nvme"``,
`utils/chunked_update.DiskChunkStore`) moves the whole optimizer state
through ``chunk_<i>/leaf_<j>.dat`` files every sync step: mmap-read each
chunk (H2D upload source), then write the updated subtree back through a
temp-file + ``os.replace``.  Step time on a disk-tier rig is set by exactly
this cycle, with the page cache doing the short-term caching — so this
microbench measures it in isolation, host-only (the TPU never touches local
disk).

Measures, at the 2.13B-geometry layout (default: 8 chunks x 1 GiB fp32):
  - initial write throughput (cold files)
  - rewrite-cycle throughput over several generations (read mmaps + write
    back + os.replace; the steady-state per-sync-step cost)
  - read throughput hot (page-cached) and after an explicit drop of the
    written pages (posix_fadvise DONTNEED best-effort)

Usage: python benchmarks/disk_tier_microbench.py [--chunks 8] [--mb 1024]
       [--cycles 3] [--path ./disk_tier_bench]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")  # host-only measurement

from accelerate_tpu.utils.chunked_update import DiskChunkStore  # noqa: E402


def _drop_page_cache(path: str):
    """Best-effort eviction of a directory's files from the page cache."""
    for dirpath, _dirs, files in os.walk(path):
        for fn in files:
            fp = os.path.join(dirpath, fn)
            fd = os.open(fp, os.O_RDONLY)
            try:
                os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
            finally:
                os.close(fd)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunks", type=int, default=8)
    ap.add_argument("--mb", type=int, default=1024, help="chunk size in MiB")
    ap.add_argument("--cycles", type=int, default=3)
    ap.add_argument("--path", default="./disk_tier_bench")
    args = ap.parse_args()

    path = os.path.abspath(args.path)
    shutil.rmtree(path, ignore_errors=True)
    store = DiskChunkStore(path)
    per_chunk = args.mb << 20
    total = args.chunks * per_chunk
    # a chunk subtree shaped like the real thing: a few leaves (masters, mu,
    # nu slices) rather than one blob
    n_leaves = 4
    leaf_elems = per_chunk // n_leaves // 4  # fp32

    rng = np.random.default_rng(0)
    # one chunk's worth of source data, reused per chunk (generation excluded
    # from the timed write)
    src = {f"leaf{j}": rng.standard_normal(leaf_elems).astype(np.float32)
           for j in range(n_leaves)}

    t0 = time.perf_counter()
    views = [store.write_chunk(i, src) for i in range(args.chunks)]
    write_s = time.perf_counter() - t0

    cycle_times = []
    for _ in range(args.cycles):
        t0 = time.perf_counter()
        new_views = []
        for i, v in enumerate(views):
            # the sync-step cycle: consume the mmaps (sum forces the read),
            # "update" (scale in fresh buffers), persist back
            updated = {k: arr * np.float32(0.999) for k, arr in v.items()}
            new_views.append(store.write_chunk(i, updated))
        views = new_views
        cycle_times.append(time.perf_counter() - t0)

    stride = 1024  # 4 KiB in fp32 — touch every page
    t0 = time.perf_counter()
    s = 0.0
    for v in views:
        s += float(sum(arr[::stride].sum() for arr in v.values()))
    hot_read_s = time.perf_counter() - t0

    _drop_page_cache(path)  # best-effort: VM-layer caches may still serve hits
    t0 = time.perf_counter()
    for i in range(args.chunks):
        v = store.read_chunk(i)
        s += float(sum(arr[::stride].sum() for arr in v.values()))
    cold_read_s = time.perf_counter() - t0

    gb = total / (1 << 30)
    steady = min(cycle_times)
    print(json.dumps({
        "metric": "disk_tier_rewrite_cycle_gbps",
        "value": round(2 * gb / steady, 2),  # read + write per cycle
        "unit": "GB/s (rd+wr)",
        "detail": {
            "state_gb": round(gb, 2),
            "chunks": args.chunks,
            "chunk_mb": args.mb,
            "initial_write_gbps": round(gb / write_s, 2),
            "cycle_s": [round(t, 2) for t in cycle_times],
            "steady_cycle_s": round(steady, 2),
            "hot_read_gbps": round(gb / hot_read_s, 2),
            "cold_read_gbps": round(gb / cold_read_s, 2),
        },
    }))
    shutil.rmtree(path, ignore_errors=True)


if __name__ == "__main__":
    main()
