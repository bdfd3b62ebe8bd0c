"""A tiny CPU rehearsal of the `fork_cold_rs14_2.scrub` cell, end to end
through the daemons: the harness's control flow, and the last line
against the contract. (One file per cell: they run on separate workers.)"""

import pytest

import bench_contract

CELL = "fork_cold_rs14_2.scrub"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_a_contract_line(trace):
    bench = bench_contract.load_benchmark()
    line = bench_contract.rehearse(CELL, trace)
    bench_contract.check_line(line, bench, CELL, trace)
