"""Benchmark driver: one function per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV rows (spec format).

  Fig 6  -> pingpong_*       (file-MPI bandwidth/latency vs message size)
  Fig 7  -> stream_triad_*   (PGAS triad GiB/s per Np)
  Fig 8  -> fft_*            (row FFT -> corner turn -> col FFT, GFLOP/s)
  Fig 9  -> randomaccess_*   (GUPS, direct messaging)
  Fig 10 -> hpl_*            (blocked LU over block-cyclic columns)
  +      -> redistribution bytes oracle (PITFALLS vs brute force)

Roofline for the 40 assigned cells is separate (needs the dry-run's 512
placeholder devices): ``python -m repro.launch.dryrun --all`` then
``python -m benchmarks.roofline``.
"""

from __future__ import annotations

import sys
import time


def _redistribution_rows() -> list[dict]:
    """PITFALLS schedule micro-bench: corner-turn message-schedule size."""
    from repro.core import Dmap
    from repro.core.jax_bridge import expected_redistribution_bytes

    rows = []
    for p in (4, 16, 64):
        row = Dmap([p, 1], {}, range(p))
        col = Dmap([1, p], {}, range(p))
        t0 = time.perf_counter()
        b = expected_redistribution_bytes((1024, 1024), 8, row, col)
        dt = time.perf_counter() - t0
        frac = b / (1024 * 1024 * 8)
        rows.append({
            "name": f"pitfalls_corner_turn_p{p}",
            "us_per_call": dt * 1e6,
            "derived": f"{frac:.4f} of array off-chip (expect {1-1/p:.4f})",
        })
    return rows


def main() -> None:
    from benchmarks import hpcc

    sections = [
        ("pingpong (Fig 6)", hpcc.bench_pingpong),
        ("stream (Fig 7)", hpcc.bench_stream),
        ("fft (Fig 8)", hpcc.bench_fft),
        ("randomaccess (Fig 9)", hpcc.bench_random_access),
        ("hpl (Fig 10)", hpcc.bench_hpl),
        ("pitfalls oracle", _redistribution_rows),
    ]
    print("name,us_per_call,derived")
    for title, fn in sections:
        print(f"# {title}", file=sys.stderr)
        for row in fn():
            print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")


if __name__ == "__main__":
    main()
