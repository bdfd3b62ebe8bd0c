"""The repair program a chip-owning volume server dispatches for a
piggyback volume, compiled for a described (not attached) TPU v5e at the
shapes the daemons use: what the chip's compiler would refuse — a tile
past the scoped VMEM, a block the sublane bitcast cannot take — fails
here, at no chip time. Nothing runs, so nothing is said about results or
speed. The compile happens in a process of its own: the TPU's library
keeps threads that would disturb this worker's timing tests."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NO_TOPOLOGY = 77

SCRIPT = r"""
import json, os, sys
os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax, jax.numpy as jnp
# a program compiled for a described chip cannot be read back from the
# persistent cache without one: keep it out
jax.config.update("jax_enable_compilation_cache", False)
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print(f"no v5e:2x2 topology can be described here: {e}")
    sys.exit(%d)
one_chip = SingleDeviceSharding(topo.devices[0])
from seaweedfs_tpu.ops import rs_pallas
from seaweedfs_tpu.ops.piggyback import PiggybackCoder
out = {}
for f in (0, 2):  # a piggyback group of four (14 rows), of three (13)
    matrix = PiggybackCoder(10, 4).repair_matrix(f)
    bmat = rs_pallas.matrix_operand(matrix)
    rows = jax.ShapeDtypeStruct((32, matrix.shape[1], 1 << 20), jnp.uint8,
                                sharding=one_chip)
    operand = jax.ShapeDtypeStruct(bmat.shape, jnp.int8, sharding=one_chip)
    mem = rs_pallas.matrix_apply_jit.lower(rows, operand).compile() \
        .memory_analysis()
    out[matrix.shape[1]] = [mem.argument_size_in_bytes,
                            mem.output_size_in_bytes, mem.temp_size_in_bytes]
print(json.dumps(out))
""" % NO_TOPOLOGY


def test_piggyback_repair_matrix_programs_compile_for_v5e():
    """[32, d + |S_g|, 1 MiB] -> [32, 2, 1 MiB] with the matrix as an
    operand, at 14 and at 13 rows."""
    r = subprocess.run([sys.executable, "-c", SCRIPT], cwd=REPO,
                       env={**os.environ, "JAX_PLATFORMS": "cpu",
                            "PYTHONPATH": REPO},
                       capture_output=True, text=True, timeout=600)
    if r.returncode == NO_TOPOLOGY:
        pytest.skip(r.stdout.strip()[-300:])
    assert r.returncode == 0, r.stderr[-3000:]
    sizes = json.loads(r.stdout.splitlines()[-1])
    assert set(sizes) == {"13", "14"}
    for rows, (args, out, temp) in sizes.items():
        assert args >= 32 * int(rows) << 20 and out == 32 * 2 << 20
        # arguments, output and temporaries fit the chip's 16 GB many times
        assert args + out + temp < 4 << 30
