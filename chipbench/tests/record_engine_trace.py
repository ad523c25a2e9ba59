#!/usr/bin/env python3
"""Record the small chip trace with the serving engine's own spans that
test_enginetrace.py reads: the smoke model under the chat cell's arrivals
on the chip, traced for its first second, kept gzipped as
chipbench/tests/data_engine/smoke_chat_engine.xplane.pb.gz (outside
data/, whose one trace test_devtrace.py reads).

    python chipbench/tests/record_engine_trace.py
"""
import gzip
import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import smoke  # noqa: E402


def main() -> int:
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_engine_trace: no TPU found", file=sys.stderr)
        return 1
    import devtrace
    import run as harness
    mix = dict(smoke.MIXES["poisson"], trace_seconds=1.0)
    arch, cfg = smoke.arch_and_cfg()
    cell = smoke.CELLS["poisson"]
    out = harness.run_cell(cell, 3, 2.0, True, cfg=cfg, arch=arch, mix=mix)
    print(json.dumps({"metrics": out["metrics"],
                      "breakdown": out["breakdown"]}))
    src = devtrace.find_xplane(harness.OUT / "trace" / cell)
    dst = HERE / "data_engine" / "smoke_chat_engine.xplane.pb.gz"
    dst.parent.mkdir(exist_ok=True)
    with open(src, "rb") as f, gzip.open(dst, "wb", compresslevel=9) as g:
        shutil.copyfileobj(f, g)
    print(dst, dst.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main())
