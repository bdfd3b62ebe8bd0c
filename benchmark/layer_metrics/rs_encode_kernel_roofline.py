"""The Pallas RS kernel through the entry a seal calls (`encode_jit`).
Needed: the `.dat` bytes of the seals that ran wholly inside the trace,
read once as d rows, and p parity rows of the same length written; the
zero padding of a seal's last `[32, d, 1 MiB]` batch is the kernel's
cost, not its work."""
from benchmark import roofline
from benchmark.layer_metrics import _shared


def read(run):
    d, p = run.config["data_shards"], run.config["parity_shards"]
    return _shared.kernel_roofline(run, ("jit_encode_jit",), _shared.total(
        [roofline.rs_ops_bytes(1, d, p, op["bytes"] / d)
         for op in run.traced_ops() if op["label"] == "seal"]))
