"""``c1024sb.calls-backlog``: the issue's numbers are in the files, the
generator's construction is counted at the deployment's width (rows a
height, the mix's shares, the hot set, the aborts, the call data's
widths), the bytecode, the plain procedures and the reference's
interpreter agree, a rehearsal on the host C++ verifier prints every check
beside its limit, each control comes out not ``correct`` by the check that
is its own, and the ``.evm`` metric files read a hand-made ``obs`` (and
nothing on a program without the spans and counters)."""

import json
import os

import pytest
from test_correct import drive, failed

from perfbench import control_contracts, gen_contracts, harness
from perfbench.drivers import smallbank
from perfbench.ref import contracts
from perfbench.ref import evm as ref_evm
from perfbench.ref.keccak import keccak256

CELL, ACCEPTOR = "c1024sb.calls-backlog", "c1024a.blocks-backlog"
CONFIG = json.load(open(os.path.join(
    harness.HERE, "configs", "committee-1024-smallbank.json")))
FULL = CONFIG["deployment"]
CHECKS = {"sound_blocks_refused", "bad_blocks_acked", "bad_blocks_inserted",
          "blocks_out_of_order", "off_chain_blocks", "acks_wrong",
          "accounts_wrong", "accounts_compared", "genesis_hash",
          "contract_state_wrong", "customers_compared",
          "evm_calls_unexplained", "evm_reverts", "blocks_inserted",
          "unanswered_rows", "wrong_answers", "valid_frames_refused",
          "invalid_frames_not_refused", "reference_mismatches",
          "reference_rows", "compiles_in_window"}


def test_the_deployments_numbers_are_the_acceptors_and_the_issues():
    cell = harness.Cell(CELL, rehearse=False)
    theirs = harness.Cell(ACCEPTOR, rehearse=False).config["deployment"]
    mine = cell.config["deployment"]
    for key in ("validators", "committee", "acceptors", "txn_per_block",
                "accounts", "senders", "cert_supporters", "gossip_window",
                "duplicate_share", "unseen_share", "invalid_every",
                "max_batch", "bad_block_every", "payload_bytes",
                "chain_blocks", "blocks_inserted_min"):
        assert mine[key] == theirs[key], key
    assert (mine["customers"], mine["hot_customers"], mine["hot_share"],
            mine["abort_every"], mine["call_gas_limit"],
            mine["block_gas_limit"]) == (100_000, 100, 0.25, 64, 100_000,
                                         2**31)
    assert (mine["balance_min"], mine["balance_max"]) == (10_000, 50_000)
    assert mine["mix"] == {"almagate": 15, "getBalance": 15,
                           "updateBalance": 15, "sendPayment": 25,
                           "updateSaving": 15, "writeCheck": 15}
    assert (cell.chips, cell.config["driver"], cell.config["reduced"]) == (
        1, "smallbank", ["cluster", "accounts", "customers"])
    assert all(isinstance(cell.config[k], str) and cell.config[k]
               for k in cell.config["reduced"])
    assert len(cell.config["guarantees"]) == 9
    assert {"customer_ids", "procedures_as_transactions", "mix_and_hot_set",
            "amounts", "abort_every", "gas", "contract_address",
            "bad_block_every"} <= set(cell.config["assumed"])
    assert "BLOCKBENCH" in cell.config["source"] \
        and "configs[3]" in cell.config["source"]
    assert cell.traffic["arrival"] == "backlog" and \
        (cell.traffic["blocks_in_flight"], cell.traffic["warm_blocks"]) \
        == (2, 2)
    # read in the ledger through the acceptor's entries
    listed = {m["name"] for m in cell.per_layer()}
    assert len([n for n in listed if n.endswith(".accept")]) == 9
    assert len([n for n in listed if n.endswith(".rows")]) == 20
    assert listed <= {m["name"] for m in harness.Cell(
        ACCEPTOR, rehearse=False).per_layer()}
    assert {m["name"] for m in cell.end_to_end()} == {"verify_rows_per_s",
                                                      "setup_s"}
    assert len(cell.bench["per_layer"]) <= 128


@pytest.fixture(scope="module")
def wide():
    """The deployment at its width (4000 calls a block, 100,000
    customers), three blocks long."""
    return gen_contracts.ContractFeed(2**31 + 21,
                                      {**FULL, "chain_blocks": 3})


def test_a_heights_rows_and_calls_at_the_deployments_width(wide):
    for p in range(3):
        c = wide.construction(p)
        assert c["rows_asked"] == (9848 if p else 9448)
        assert (c["gossip_frames"], c["copies"], c["spoiled"]) == (
            5333 if p else 4933, 1250, 83)
        assert c["steps"] == [("request", 4001, True), ("confirm", 514, True)]
        assert sum(c["calls"].values()) == 4000
        # the mix's shares (one call in 64 is made a sendPayment)
        for name, share in FULL["mix"].items():
            assert abs(c["calls"][name] / 4000 - share / 100) < 0.03, name
        assert 0.22 < c["hot_share"] < 0.28
        assert c["aborted"] >= 4000 // 64
        assert c["call_data_bytes"] == [36, 68, 100]
        assert 2500 < c["slots_written"] < 7000 and c["slots_deleted"] > 0
        assert 1500 < c["touched_accounts"] <= 2048
    placed = [k for k in range(12000) if k % 64 == 63]
    assert all(wide.aborted[k] and wide.calls[k][0] == "sendPayment"
               for k in placed)
    name, (payer, payee, amount) = wide.calls[63]
    assert payer != payee and len(contracts.call_data(
        name, payer, payee, amount)) == FULL["payload_bytes"] == 100
    assert len(wide.genesis_storage) == 200_000
    assert all(10_000 <= v <= 50_000 for v in wide.genesis_storage.values())
    # a receipt's gas is upstream's: about 32,600 a sendPayment that runs
    statuses, gas = wide.receipts[1]
    used = [g - (gas[i - 1] if i else 0) for i, g in enumerate(gas)]
    pays = [u for u, s, (n, _a) in zip(used, statuses,
                                       wide.calls[4000:8000])
            if n == "sendPayment" and s]
    assert 15_000 < min(pays) and max(pays) < 50_000
    assert 32_000 < sorted(pays)[len(pays) // 2] < 33_500
    assert all(h["gas_limit"] == 2**31 for h in wide.headers)


def test_the_bytecode_the_procedures_and_the_interpreter_agree():
    """Every procedure from call data under ``ref/evm.py`` against
    ``Bank``: the slots left, who aborts, and the gas of a kind of call."""
    bank = contracts.Bank({1: 100, 2: 7}, {1: 50, 2: 3})
    storage = {contracts.slot_of(c, m): v for m, book in
               enumerate((bank.saving, bank.checking))
               for c, v in book.items()}
    calls = [("sendPayment", 1, 2, 5), ("sendPayment", 2, 1, 9),
             ("almagate", 1, 2), ("getBalance", 2), ("updateBalance", 1, 4),
             ("updateSaving", 2, 20), ("writeCheck", 1, 5),
             ("writeCheck", 2, 10**6), ("sendPayment", 1, 1, 1)]
    for name, *args in calls:
        status, used, writes = ref_evm.apply_call(
            contracts.SMALLBANK, contracts.call_data(name, *args), storage,
            100_000, keccak256)
        try:
            getattr(bank, name)(*args)
            assert status == 1, name
        except contracts.Aborted:
            assert status == 0 and not writes, name
        for slot, value in writes.items():
            storage.pop(slot, None)
            if value:
                storage[slot] = value
        assert storage == {contracts.slot_of(c, m): v for m, book in
                           enumerate((bank.saving, bank.checking))
                           for c, v in book.items()}, name
        # (a cleared slot's refund may take a call under its intrinsic gas)
        assert ref_evm.intrinsic_gas(contracts.call_data(name, *args)) // 2 \
            < used < 100_000
    assert ref_evm.run(contracts.SMALLBANK, b"\x00" * 4, {}, 1000,
                       keccak256).status == 0  # no such selector: REVERT


def test_same_seed_same_chain_and_every_seed_the_same_counts():
    tiny = {**FULL, **CONFIG["rehearse"]}
    a, b = (gen_contracts.ContractFeed(2**31 + 9, tiny) for _ in range(2))
    c = gen_contracts.ContractFeed(2**31 + 10, tiny)
    assert [[s.data for s in st] for st in a.steps] == \
        [[s.data for s in st] for st in b.steps]
    assert a.frames == b.frames and a.blocks == b.blocks
    assert a.block_hashes != c.block_hashes
    for p in range(12):
        ca, cc = a.construction(p), c.construction(p)
        for key in ("gossip_frames", "own_in_time", "late_of_previous",
                    "copies", "spoiled", "bad", "steps", "rows_asked"):
            assert ca[key] == cc[key], (p, key)
    # the five kinds in turn, each followed by its height's sound block
    assert [a.bad[p] for p in sorted(a.bad)][:5] == list(
        gen_contracts.BAD_KINDS)


def test_a_rehearsal_prints_every_check_beside_its_limit():
    rc, line, err = drive(workload=CELL)
    assert failed(line) == []
    assert rc != 0 and line["correct"] is False and line["rehearsal"]
    assert set(line["checks"]) >= CHECKS
    assert set(line["metrics"]) == {"verify_rows_per_s", "setup_s"}
    for name in line["checks"]:
        assert f"check {name}: " in err
    info = json.loads(next(ln for ln in err.splitlines()
                           if ln.startswith("info "))[5:])
    assert info["blocks_inserted"] >= 4 and info["bad_heights_in_window"]
    assert set(smallbank.EVM_FILES) <= set(info["unlisted"])


@pytest.mark.parametrize("control, check", [
    ("keep_reverted", "contract_state_wrong"),
    ("accept_all", "bad_blocks_acked"),
])
def test_each_control_fails_by_the_check_that_is_its_own(control, check):
    assert control in control_contracts.NAMES
    _, line, _ = drive("--control", control, workload=CELL)
    assert line["correct"] is False
    assert check in failed(line)
    # the program is left as it was
    assert failed(drive(workload=CELL)[1]) == []


def test_the_evm_metrics_read_the_spans_and_the_counters():
    hist = lambda n, mean: {"count": n, "mean": mean}  # noqa: E731
    span = lambda name: f"span.self_seconds;name={name}"  # noqa: E731
    snap = lambda n: {  # noqa: E731
        span("chain.execute"): hist(n, 0.600),
        span("state.storage_root"): hist(n, 0.002),
        "evm.calls": 4000 * n, "evm.ops": 240_000 * n,
        "evm.reverts": 100 * n, "evm.sstores": 5000 * n,
        "chain.executions": n, "chain.blocks": n,
        "trie.store_nodes": 50_000.0 * n}
    obs = {"before": snap(10), "after": snap(30), "window_s": 40.0,
           "samples": {}, "flights": [], "trace": None, "t_begin": 0.0,
           "t_end": 40.0}
    assert smallbank._evm_readings(obs) == {
        "evm_us_per_call.evm": 150.0, "evm_ops_per_call.evm": 60.0,
        "evm_revert_share.evm": 2.5, "storage_writes_per_block.evm": 5000.0,
        "storage_root_share.evm": 0.1, "store_nodes_per_height.evm": 50000.0}
    # a program without the spans and the counters: nothing, never 0
    old = {"before": {}, "after": {}, "window_s": 40.0, "samples": {},
           "flights": [], "trace": None, "t_begin": 0.0, "t_end": 40.0}
    assert smallbank._evm_readings(old) == {}
    for name in smallbank.EVM_FILES:
        spec = harness.metric_file(name)
        assert spec["suffixes"][".evm"]["moves"] == "verify_rows_per_s"
        assert spec["unit"] and spec["source"] in ("program_span",
                                                   "program_counter")
