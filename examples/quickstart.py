"""Quickstart: decode a batch of JPEGs fully on-device (the paper's API).

    PYTHONPATH=src python examples/quickstart.py

Builds a small synthetic dataset, decodes it with the parallel decoder
(jacobi sync), verifies bit-exactness against the strict sequential oracle,
and prints the paper-style throughput numbers.
"""
import sys
import time

sys.path.insert(0, "src")

import numpy as np

from repro.core import ParallelDecoder
from repro.jpeg import codec_ref
from repro.jpeg.encoder import DatasetSpec, build_dataset
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    spec = DatasetSpec("quickstart", n_images=16, width=320, height=192,
                       quality=85, subsampling="4:2:0",
                       subsequence_bits=1024)
    print(f"encoding {spec.n_images} images ({spec.width}x{spec.height}, "
          f"q={spec.quality})...")
    ds = build_dataset(spec, keep_truth=True)
    print(f"compressed: {ds.compressed_mb:.2f} MB "
          f"({ds.avg_image_kb:.0f} KB/image)")

    dec = ParallelDecoder.from_bytes(ds.jpeg_bytes,
                                     chunk_bits=spec.subsequence_bits)
    print(f"plan: {dec.plan.n_chunks} subsequences of "
          f"{dec.plan.chunk_bits} bits across {dec.plan.n_segments} segments")

    t0 = time.perf_counter()
    out = dec.decode(emit="rgb")
    out.rgb.block_until_ready()
    dt = time.perf_counter() - t0
    print(f"decoded in {dt*1e3:.0f} ms "
          f"({ds.compressed_mb / dt:.1f} MB/s compressed, "
          f"sync converged in {out.sync_rounds} rounds)")

    # bit-exactness vs the sequential oracle (entropy level)
    exp = np.concatenate([
        codec_ref.undiff_dc(r_img := codec_ref.parse_jpeg(b),
                            codec_ref.decode_coefficients(r_img))
        for b in ds.jpeg_bytes
    ])
    assert np.array_equal(np.asarray(out.coeffs), exp), "coefficient mismatch!"
    print("bit-exact vs sequential oracle: OK")
    print("decoded batch:", out.rgb.shape, out.rgb.dtype)


if __name__ == "__main__":
    main()
