"""Records `stages.xplane.pb`: one process that owns the chip seals two
small volumes (three `[4, 14, 1 MiB]` batches), scrubs a volume of small
needles and rebuilds one lost shard, with the JAX profiler on (Python
tracer off, as `launch_a.py` has it). The program's stage annotations
(`swtpu/ec.*`, `swtpu/scrub.*`, `swtpu/rebuild.*`) land in the trace
beside the device's programs; `tests/benchmark_harness/
test_benchmark_host_spans.py` reduces it.

    chiprun -- python benchmark/testdata/record_stages.py

writes `chiprun_out/stages.xplane.pb`. Every shape is run once before
the trace starts, so the trace holds no compile.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, CHECKOUT)

SEED = 25
BATCH = 4  # slabs a device call: three batches for 2 x 6 rows


def main() -> int:
    import jax.profiler

    from benchmark import data
    from seaweedfs_tpu.ec import encoder, files, stream
    from seaweedfs_tpu.ec.locate import EcGeometry
    from seaweedfs_tpu.ops import device
    from seaweedfs_tpu.ops.coder import get_coder
    from seaweedfs_tpu.storage.scrub import scrub_volume
    from seaweedfs_tpu.storage.volume import Volume

    root = tempfile.mkdtemp(prefix="record_stages_")
    geo = EcGeometry(14, 2, 1 << 30, 1 << 20)
    coder = get_coder("jax", geo.d, geo.p)
    # `on` demands the TPU; a try-out of this script on the CPU backend
    # (JAX_PLATFORMS=cpu) takes what it is given
    scrub_on = "on" if device.info().platform == "tpu" else "auto"
    big = {"min": 1024, "max": 262144, "fill_bytes": 80 << 20}
    small = {"min": 1024, "max": 4096, "fill_bytes": 1 << 20}
    data.write_volumes(root, "c14", [1, 2], SEED, big)
    data.write_volume(root, "c14", 3, SEED, small)
    jobs = [(os.path.join(root, f"c14_{v}.dat"),
             os.path.join(root, f"c14_{v}"),
             os.path.join(root, f"c14_{v}.idx")) for v in (1, 2)]
    lost = jobs[0][1] + files.shard_ext(3)

    def work() -> dict:
        stats: dict = {}
        stream.encode_volumes(jobs, geo, coder, batch=BATCH, stats=stats)
        v = Volume(root, "c14", 3, create_if_missing=False)
        try:
            res = scrub_volume(v, device=scrub_on)
        finally:
            v.close()
        os.unlink(lost)
        rebuilt: dict = {}
        encoder.rebuild_shards(jobs[0][1], geo, coder, batch=BATCH,
                               stats=rebuilt)
        return {"encode": stats, "scrub": res, "rebuild": rebuilt}

    work()  # every program compiled and loaded
    trace_dir = os.path.join(root, "trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    got = work()
    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))[-1]
    out_dir = os.path.join(CHECKOUT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, "stages.xplane.pb")
    shutil.copyfile(found, out)
    print(f"{out}: {os.path.getsize(out)} bytes; encode {got['encode']}; "
          f"scrub {got['scrub']}; rebuild {got['rebuild']}")
    shutil.rmtree(root, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
