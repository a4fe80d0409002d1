"""Is host->device bandwidth per-stream or physical?

Measures device_put throughput for the bench.py round payload (2.1 MB)
with 1 vs 2 concurrent transfer threads.  If the h2d rate is a
per-stream limit, two streams should scale and bench.py's single-thread
xfer pool is leaving throughput on the table; if it is a physical rate,
two streams will split it and the current pipeline shape is already
optimal.  Not yet run on the chip (ROADMAP S2).

Run only on a machine with a chip: python scripts/exp_xfer_streams.py
"""

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def main():
    import jax

    rng = np.random.default_rng(7)
    # Two distinct buffers per stream so caching can't fake a win.
    bufs = [rng.integers(0, 256, size=(16, 1024, 130), dtype=np.uint8)
            for _ in range(4)]
    mb = bufs[0].nbytes / 1e6

    jax.device_put(bufs[0]).block_until_ready()  # warm the path

    def put(buf):
        x = jax.device_put(buf)
        x.block_until_ready()
        return x

    for streams in (1, 2):
        best = 0.0
        for trial in range(4):
            with ThreadPoolExecutor(streams) as pool:
                t0 = time.perf_counter()
                futs = [pool.submit(put, bufs[(trial + i) % 4])
                        for i in range(2 * streams)]
                for f in futs:
                    f.result()
                dt = time.perf_counter() - t0
            rate = 2 * streams * mb / dt
            best = max(best, rate)
        print(f"streams={streams}: best {best:.1f} MB/s "
              f"({2 * streams} x {mb:.1f} MB)")


if __name__ == "__main__":
    main()
