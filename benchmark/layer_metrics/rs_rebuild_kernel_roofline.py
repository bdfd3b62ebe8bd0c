"""The Pallas RS kernel through the entry a rebuild calls
(`reconstruct_jit`). Needed: for each rebuild that ran wholly inside the
trace, d surviving shard files read once and as many written as were
lost."""
from benchmark import reference, roofline
from benchmark.layer_metrics import _shared


def read(run):
    d = run.config["data_shards"]
    return _shared.kernel_roofline(
        run, ("jit_reconstruct_jit",), _shared.total(
            [roofline.rs_ops_bytes(1, d, len(op["lost"]),
                                   reference.shard_file_size(op["bytes"], d))
             for op in run.traced_ops() if op["label"] == "repair"]))
