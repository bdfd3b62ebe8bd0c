"""A tiny CPU rehearsal of the `warehouse_hh_rs10_4.codec_repair` cell,
end to end through the daemons: the harness's control flow, the warm-up
on the ring's own volumes, the bytes-read rule of `correct`, and the last
line against the contract. (One file per cell: they run on separate
workers.)"""

import pytest

import bench_contract

CELL = "warehouse_hh_rs10_4.codec_repair"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_a_contract_line(trace):
    bench = bench_contract.load_benchmark()
    line = bench_contract.rehearse(CELL, trace)
    bench_contract.check_line(line, bench, CELL, trace)
    if trace:  # a count, so a number on the CPU too: 6.5 or 7.0 a repair
        got = line["metrics"]["hh_repair_read_amplification"]["value"]
        assert 6.5 <= got <= 7.0
