"""Launch the north-star streaming workload: N rows of 224x224 images
through ImageFeaturizer without ever materializing the dataset.

BASELINE.md's headline config is ResNet-50 featurization over a 1M-row
DataFrame (~150 GB of pixels — far beyond host memory); the reference
streams partitions from disk (io/binary/BinaryFileFormat.scala:112-149).
Here the source is a StreamingDataFrame of synthetic image chunks, so the
full-size run is LAUNCHABLE on any host and the featurize path sees
exactly the production shapes.

  PYTHONPATH=. python tools/northstar_stream.py                 # 1M rows
  PYTHONPATH=. JAX_PLATFORMS=cpu python tools/northstar_stream.py \
      --rows 512 --chunk 128 --size 32 --model ResNet8_Digits   # smoke
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.io.stream import StreamingDataFrame
from mmlspark_tpu.models import ImageFeaturizer


def run(rows: int, chunk: int, size: int, model: str, batch: int) -> dict:
    n_chunks = (rows + chunk - 1) // chunk

    def make_chunk(i: int) -> DataFrame:
        # deterministic per-chunk synthesis — nothing persists across chunks
        rng = np.random.default_rng(i)
        n = min(chunk, rows - i * chunk)
        imgs = rng.integers(0, 255, size=(n, size, size, 3), dtype=np.uint8)
        return DataFrame.from_dict({"image": imgs})

    stream = StreamingDataFrame.from_generator(make_chunk, num_chunks=n_chunks)
    feat = ImageFeaturizer(
        input_col="image", output_col="features",
        model_name=model, batch_size=batch, image_size=size,
    )
    t0 = time.perf_counter()
    done = [0]

    def sink(out: DataFrame) -> None:
        _ = out["features"]  # materialize the chunk's features, then drop
        done[0] += len(out)
        if done[0] % (chunk * 8) < chunk:
            dt = time.perf_counter() - t0
            print(f"  {done[0]}/{rows} rows  {done[0] / dt:.1f} img/s", flush=True)

    total = stream.transform(feat).foreach_chunk(sink)
    dt = time.perf_counter() - t0
    return {"rows": total, "seconds": round(dt, 2), "images_per_sec": round(total / dt, 2)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--chunk", type=int, default=2048)
    ap.add_argument("--size", type=int, default=224)
    ap.add_argument("--model", default="ResNet50")
    ap.add_argument("--batch", type=int, default=256)
    args = ap.parse_args()
    from mmlspark_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    print(run(args.rows, args.chunk, args.size, args.model, args.batch))


if __name__ == "__main__":
    main()
