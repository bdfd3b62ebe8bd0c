"""The reader of `<cell>_verb_executor_share`: the share of a window's
timed verbs that ran in the master's resident shell executor, counted by
the `shell via=executor|process` line each `shell -c` process writes
before its `process` line. Counts on synthetic operations; None, never an
error, where no verb writes the line (a program without the executor);
and the four traced rehearsals, where every verb runs in the executor."""

import types

import pytest

import bench_contract
from benchmark.run import load_reader

READERS = bench_contract.BENCH + "/layer_metrics"
CELLS = {"seal": "fork_cold_rs14_2.seal",
         "repair": "upstream_rs10_4.repair",
         "hh_repair": "warehouse_hh_rs10_4.codec_repair",
         "scrub": "fork_cold_rs14_2.scrub"}
NAMES = tuple(f"{prefix}_verb_executor_share" for prefix in CELLS)
PROCESS = ("process start=0.080 imports=0.020 commands=1.500 between=0.001 "
           "release=0.002 birth_ns=1792000000000000000 "
           "traces=0af7651916cd43dd8448eb211c80319c")


def verb(via, label="repair", process=True):
    out = "rebuilt 1 shards\ntiming ec.rebuild total=1.500 rpc=1.400\n"
    if via:
        out += f"shell via={via}\n"
    if process:
        out += PROCESS + "\n"
    return {"label": label, "wall_s": 1.7, "rc": 0, "out": out}


def read(name, ops):
    return load_reader(READERS, name).read(types.SimpleNamespace(ops=ops))


def test_the_4_entries_are_read_by_one_file():
    bench = bench_contract.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"] if m["name"] in NAMES}
    assert set(entries) == set(NAMES)
    files = {load_reader(READERS, name).__spec__.origin for name in NAMES}
    assert {f.rsplit("/", 1)[1] for f in files} == {
        "verb_executor_share.py"}
    for name, m in entries.items():
        cell = CELLS[name[:-len("_verb_executor_share")]]
        assert m["workloads"] == [cell]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "%", "higher", "program_counter", "shell verbs to RPC")
        (moves,) = [e for e in bench["end_to_end"] if e["name"] == m["moves"]]
        assert cell in moves["workloads"]
    # appended after every entry the benchmark had
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(NAMES)


@pytest.mark.parametrize("vias,share", [
    (("executor", "executor"), 100.0),
    (("executor", "process"), 50.0),
    (("process", "process", "process"), 0.0),
    # a verb whose `via` line is missing counts against the executor
    (("executor", None, "executor", "executor"), 75.0)])
def test_the_share_counts_verbs(vias, share):
    assert read("repair_verb_executor_share",
                [verb(v) for v in vias]) == pytest.approx(share)


def test_only_the_windows_verbs_with_a_process_line_count():
    ops = [verb("executor"), verb("process", label="warm"),
           verb("process", process=False)]
    assert read("scrub_verb_executor_share", ops) == pytest.approx(100.0)


@pytest.mark.parametrize("ops", [
    [], [verb(None)], [verb(None), verb(None)],
    [verb("executor", process=False)]])
def test_none_without_the_line(ops):
    assert read("seal_verb_executor_share", ops) is None


@pytest.mark.parametrize("prefix", sorted(CELLS))
def test_traced_rehearsal_runs_every_verb_in_the_executor(prefix):
    cell = CELLS[prefix]
    got = bench_contract.rehearse(cell, 1)
    bench_contract.check_line(got, bench_contract.load_benchmark(), cell, 1)
    assert got["metrics"][f"{prefix}_verb_executor_share"] == {
        "value": 100.0, "unit": "%"}
