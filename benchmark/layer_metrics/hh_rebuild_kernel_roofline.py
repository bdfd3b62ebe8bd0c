"""The GF(2^8) kernel under a Hitchhiker single data-shard repair, against
what the ALGORITHM needs, whatever implements it. Needed, for each rebuild
that ran wholly inside the trace, of a shard of S bytes in a group S_g:
(d + |S_g|) half-shards read once and two written, each byte counted
once (not once an apply), and the operations of two [1, d] GF applies
over S / 2 columns (b_f from d b-halves, P_g(b) from the d of the whole
substripe; the XORs are not counted). Over the device time of every
program a rebuild runs: `reconstruct_jit` (two calls a window with the
repair as two steps) and `matrix_apply_jit` (the repair as one matrix)."""
from benchmark import reference, roofline
from benchmark import reference_hitchhiker as hh
from benchmark.layer_metrics import _shared


def read(run):
    d, p = run.config["data_shards"], run.config["parity_shards"]
    work = []
    for op in run.traced_ops():
        if op["label"] != "repair" or len(op["lost"]) != 1:
            continue
        half = reference.shard_file_size(op["bytes"], d) // 2
        ops, _ = roofline.rs_ops_bytes(1, d, 1, half)
        work.append((2 * ops,
                     float((len(hh.reads(op["lost"][0], d, p)) + 2) * half)))
    return _shared.kernel_roofline(
        run, ("jit_reconstruct_jit", "jit_matrix_apply_jit"),
        _shared.total(work))
