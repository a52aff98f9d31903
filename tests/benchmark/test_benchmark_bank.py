"""The bank's cell (`bank-ordered-1m`) at a toy size on the CPU: end to end,
untraced and traced, under both slots families (the CPU's own and the one
the chip runs); the reference's rule against a per-mailbox Python queue; the
controls, each failing by the limits named for it and no other; faults
planted under the timed path, each coming out as not correct by the limit
named for it; and the rooflines' byte counts against a hand count."""

import json
import os
import time

import numpy as np
import pytest

import bench_tiny
from benchmark import harness, peaks, rooflines_bank, xplane
from benchmark.harness import BENCH, load_json
from benchmark.reference import bank, bank_controls

CELL = "bank-ordered-1m"
CONFIG = "benchmark/configs/bank-accounts-128k.json"
TRAFFIC = load_json(BENCH, "traffic", "bank-commands.json")
CONF = load_json(BENCH, "configs", "bank-accounts-128k.json")
LIMITS = CONF["limits"]
JUDGED = set(LIMITS) - {"compiles_in_window"}  # what the reference judges
# the CPU's `auto` is the ranked family; the chip's is the wide one
FAMILIES = {"auto": {}, "wide": {"delivery_backend": "reference"}}


@pytest.fixture()
def root(tmp_path, monkeypatch):
    # as tests/benchmark/test_benchmark_cells.py: the CPU is lent a row of
    # peaks, and its operations are made into a device plane
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    monkeypatch.setattr(xplane, "load",
                        bench_tiny.load_cpu_trace_as_device(xplane.load))
    return bench_tiny.tiny_root(tmp_path)


def execute(root, trace=False, seed=2 ** 31 + 77, faults=None, seconds=1.0):
    return harness.execute(CELL, seed, seconds, trace, time.monotonic(),
                           require_chip=False, root=root, faults=faults)


def with_family(root, family):
    if FAMILIES[family]:
        bench_tiny._shrink(root, CONFIG, {"builder_args": FAMILIES[family]})


def verdict(numbers):
    return all(c["value"] <= c["limit"] for c in numbers.values())


def wrong_of(numbers):
    return {k for k, c in numbers.items() if c["value"] > c["limit"]}


# ------------------------------------------------------------- the cell
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_cell_end_to_end_at_toy_size(root, family):
    with_family(root, family)
    res = execute(root)
    assert res["correct"] is True, res["compared"]
    assert set(res["compared"]) == set(LIMITS) and len(LIMITS) == 10
    assert all(c["value"] == 0 for c in res["compared"].values())
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"tells_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["count"] == 1
    # the toy's mailboxes overflow as the cell's do: the spill carried mail
    assert res["setup_parts_s"]["spill"]["spilled"] > 0


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_traced_run_reports_every_bank_metric(root, family):
    with_family(root, family)
    res = execute(root, trace=True)
    assert res["correct"] is True, res["compared"]
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        man = json.load(f)
    mine = {m["name"] for m in man["per_layer"] if CELL in m["workloads"]}
    assert set(res["metrics"]) == mine and len(mine) == 9
    assert all(name.startswith("bank_") for name in mine)
    values = {k: v["value"] for k, v in res["metrics"].items()}
    assert values["bank_step_ms"] > 0
    assert values["bank_place_share"] > 0 and values["bank_spill_share"] > 0
    assert values["bank_place_roofline"] > 0
    assert values["bank_behavior_share"] > 0  # the fold's scan lives there
    # the blocks are parts of their layer, the layers parts of one busy time
    assert values["bank_place_share"] + values["bank_spill_share"] \
        <= values["bank_deliver_share"] + 1e-6
    layers = [values[f"bank_{k}_share"]
              for k in ("deliver", "behavior", "unscoped")]
    assert sum(layers) <= 100 + 1e-6
    assert 0 <= values["bank_device_idle_share"] <= 100


def test_scope_table_names_the_slots_blocks_and_the_spills_write(root, capfd):
    bench_tiny._shrink(root, CONFIG, {"builder_args": FAMILIES["wide"]})
    execute(root, trace=True)
    table = capfd.readouterr().err
    for block in ("akka.deliver.sort", "akka.deliver.place",
                  "akka.deliver.spill", "akka.deliver.prefix",
                  "akka.behavior.account", "akka.emit.spill"):
        assert f"    {block} " in table, (block, [
            line for line in table.splitlines() if "akka." in line[:40]])


def test_block_reader_on_a_program_without_the_block_reads_nothing(root):
    """The new reader on a program that lacks the block: nothing to read,
    and no exception (a cell on reduce delivery, as every cell of the
    parent)."""
    from benchmark.readers import block_roofline_of
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        man = json.load(f)
    bench_tiny.add_file(root, "benchmark/metrics/fanin_place_roofline.json",
                        load_json(BENCH, "metrics",
                                  "bank_place_roofline.json"))
    man["per_layer"].append({
        "name": "fanin_place_roofline", "unit": "%", "better": "higher",
        "source": "program_span", "layer": "deliver", "moves": "tells_per_s",
        "workloads": ["fanin-1m-1k"]})
    bench_tiny.write_manifest(root, man)
    res = harness.execute("fanin-1m-1k", 5, 1.0, True, time.monotonic(),
                          require_chip=False, root=root)
    assert res["correct"] is True
    assert "fanin_place_roofline" not in res["metrics"]
    assert "fanin_step_ms" in res["metrics"]
    assert block_roofline_of.read(
        {"steps_in_trace": 0}, [], "akka.deliver", "akka.deliver.place",
        "rooflines_bank", "bank_place_bytes") is None


# -------------------------------------------------------- the reference
def a_run(n_accounts=64, n_tellers=192, slots=4, spill=256, seed=11,
          steps=(8, 24, 8)):
    """Three readings of a run by the reference itself, laid out as the
    system lays its inbox out (the spill region first)."""
    tellers = bank.seed_tellers(n_tellers, n_accounts, seed, TRAFFIC)
    accounts, queue = bank.zero_accounts(n_accounts), bank.empty_queue()
    got, t = {"dropped": 0}, 0
    for when, k in zip(("open", "close", "after"), steps):
        accounts, queue, carried = bank.replay(accounts, queue, t, k, tellers,
                                               TRAFFIC, slots)
        t += k
        snap = bank_controls._reading(accounts, queue, t, tellers)
        left = int(carried[-1])
        for name in ("inbox_dst", "inbox_type", "inbox_payload",
                     "inbox_valid"):
            v = snap[name]
            gap = np.zeros((spill - left,) + v.shape[1:], v.dtype)
            snap[name] = np.concatenate([v[:left], gap, v[left:]])
        got[when] = snap
    return tellers, got


def python_queues(n_accounts, n_tellers, slots, seed, steps):
    """The paragraph of benchmark/reference/bank.py's docstring as one
    Python queue an account: no numpy in the rule."""
    tellers = bank.seed_tellers(n_tellers, n_accounts, seed, TRAFFIC)
    home, stride, mask, c = (tellers[k].tolist() for k in
                             bank.TELLER_COLUMNS)
    period, levels = TRAFFIC["period"], TRAFFIC["levels"]
    state = [dict.fromkeys(bank.ACCOUNT_COLUMNS, 0)
             for _ in range(n_accounts)]
    waiting = [[] for _ in range(n_accounts)]
    fresh, carried = [], []
    for t in range(steps):
        for account, command in fresh:  # by teller row, behind the leftovers
            waiting[account].append(command)
        left = 0
        for account, queue in enumerate(waiting):
            s = state[account]
            for kind, amount, teller in queue[:slots]:
                if kind == bank.DEPOSIT:
                    s["balance"] += amount
                    s["deposited"] += amount
                elif s["balance"] >= amount:
                    s["balance"] -= amount
                    s["withdrawn"] += amount
                else:
                    s["rejected"] += 1
                s["applied"] += 1
                s["last_teller"] = teller
            del queue[:slots]
            left += len(queue)
        carried.append(left)
        p = t % period
        fresh = []
        for i in range(n_tellers):
            withdraw = (mask[i] >> p) & 1
            fresh.append(((home[i] + stride[i] * p) % n_accounts,
                          (bank.WITHDRAW if withdraw else bank.DEPOSIT,
                           1 + withdraw + (c[i] + t) % levels,
                           n_accounts + i)))
    return state, waiting, fresh, carried


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_reference_step_is_the_queue_rule_one_command_at_a_time(seed):
    n_accounts, n_tellers, slots, steps = 32, 96, 4, 40
    state, waiting, fresh, carried = python_queues(n_accounts, n_tellers,
                                                   slots, seed, steps)
    tellers = bank.seed_tellers(n_tellers, n_accounts, seed, TRAFFIC)
    accounts, queue, left = bank.replay(
        bank.zero_accounts(n_accounts), bank.empty_queue(), 0, steps, tellers,
        TRAFFIC, slots)
    for k in bank.ACCOUNT_COLUMNS:
        assert accounts[k].tolist() == [s[k] for s in state], k
    assert left.tolist() == carried and sum(carried) > 0
    rows = [(a,) + command for a, q in enumerate(waiting) for command in q] \
        + [(a,) + command for a, command in fresh]
    have = list(zip(*(queue[k].tolist() for k in bank.QUEUE_COLUMNS)))
    assert have == rows
    assert sum(s["rejected"] for s in state) > 0  # order matters here


def test_the_traffic_is_what_the_issue_names():
    n_accounts, n_tellers = 1 << 12, 1 << 15
    tellers = bank.seed_tellers(n_tellers, n_accounts, 9, TRAFFIC)
    assert (tellers["stride"] % 2 == 1).all()
    assert 0 <= tellers["home"].min() and tellers["home"].max() < n_accounts
    assert tellers["c"].min() == 0 and tellers["c"].max() == 7
    bits = (tellers["mask"][:, None] >> np.arange(16)) & 1
    assert abs(bits.mean() - 0.5) < 0.01
    told = [bank.emissions(t, tellers, n_accounts, TRAFFIC)
            for t in range(32)]
    for t, e in enumerate(told):  # one tell a teller a step
        assert e["account"].shape == (n_tellers,)
        withdraw = e["kind"] == bank.WITHDRAW
        assert ((e["kind"] == bank.DEPOSIT) | withdraw).all()
        assert (e["amount"] == 1 + withdraw + (tellers["c"] + t) % 8).all()
        assert (e["teller"] == n_accounts + np.arange(n_tellers)).all()
    # a period of 16: the same account and command kind, 16 steps on
    assert (told[3]["account"] == told[19]["account"]).all()
    assert (told[3]["kind"] == told[19]["kind"]).all()
    assert (told[3]["account"] != told[4]["account"]).mean() > 0.99
    # a teller visits 16 distinct accounts in a period
    visits = np.stack([e["account"] for e in told[:16]], axis=1)
    assert all(len(set(row)) == 16 for row in visits[:200].tolist())
    # eight commands a mailbox a step on average; the drift is -0.5
    per_box = np.bincount(told[0]["account"], minlength=n_accounts)
    assert abs(per_box.mean() - 8) < 1e-9 and per_box.max() > 16
    amounts = np.concatenate([np.where(e["kind"] == bank.DEPOSIT, 1, -1)
                              * e["amount"] for e in told])
    assert abs(amounts.mean() + 0.5) < 0.05
    other = bank.seed_tellers(n_tellers, n_accounts, 10, TRAFFIC)
    assert (other["home"] != tellers["home"]).any()


def test_totals_are_compared_modulo_two_to_the_32():
    tellers, got = a_run()
    numbers = bank.judge(tellers, TRAFFIC, 64, 4, got, LIMITS)
    assert verdict(numbers) and set(numbers) == JUDGED
    for when in ("open", "close", "after"):  # as columns that wrapped
        acc = got[when]["accounts"]
        acc["deposited"] = acc["deposited"] + 2 ** 32
        acc["withdrawn"] = acc["withdrawn"] - 2 ** 32 * np.arange(64)
    assert verdict(bank.judge(tellers, TRAFFIC, 64, 4, got, LIMITS))
    got["after"]["accounts"]["withdrawn"][5] += 1
    assert wrong_of(bank.judge(tellers, TRAFFIC, 64, 4, got, LIMITS)) == {
        "accounts_wrong", "ledger_wrong"}


def test_the_queue_is_compared_row_for_row_and_malformed_rows_count():
    tellers, got = a_run()
    rows = np.flatnonzero(got["after"]["inbox_valid"])
    a, b = rows[-1], rows[-2]
    for k in ("inbox_dst", "inbox_type", "inbox_payload"):
        got["after"][k][[a, b]] = got["after"][k][[b, a]]
    numbers = bank.judge(tellers, TRAFFIC, 64, 4, got, LIMITS)
    assert wrong_of(numbers) == {"queue_wrong"}
    assert numbers["queue_wrong"]["value"] == 2
    tellers, got = a_run()
    got["after"]["inbox_payload"][rows[0], 0] = 2.5  # no whole amount
    numbers = bank.judge(tellers, TRAFFIC, 64, 4, got, LIMITS)
    assert numbers["queue_wrong"]["value"] >= 1


# ---------------------------------------------------------- the controls
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_reference_in_the_programs_place_is_correct(seed):
    tellers, got = a_run(seed=seed)
    out = bank_controls.judge_controls(tellers, TRAFFIC, 64, 4, 256, got,
                                       LIMITS)
    assert verdict(out["reference_itself"])
    assert set(out["reference_itself"]) == JUDGED
    assert set(out) == set(bank_controls.CONTROLS) | {"reference_itself"}
    assert set(bank_controls.CAUGHT_BY) == set(bank_controls.CONTROLS)


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("control", sorted(bank_controls.CONTROLS))
def test_control_with_one_guarantee_broken_is_not_correct(control, seed):
    tellers, got = a_run(seed=seed)
    numbers = bank_controls.judge_controls(tellers, TRAFFIC, 64, 4, 256, got,
                                           LIMITS)[control]
    assert not verdict(numbers)
    must, may = bank_controls.CAUGHT_BY[control]
    assert must <= wrong_of(numbers) <= must | may  # and by no other
    assert bank_controls.caught_as_named(control, numbers)


def test_every_limit_is_tripped_by_some_control_that_must():
    named = set().union(*(must for must, _ in
                          bank_controls.CAUGHT_BY.values()))
    assert named == JUDGED


def test_controls_refuse_a_run_they_cannot_break():
    tellers, got = a_run(steps=(8, 24, 1))
    with pytest.raises(ValueError, match="two steps"):
        bank_controls.judge_controls(tellers, TRAFFIC, 64, 4, 256, got,
                                     LIMITS)
    # mailboxes that never overflow leave no spill to lose or to reorder
    tellers, got = a_run(n_tellers=32, slots=16)
    with pytest.raises(bank_controls.NothingToBreak):
        bank_controls.judge_controls(tellers, TRAFFIC, 64, 16, 256, got,
                                     LIMITS)


def test_control_tool_runs_the_bank_controls(root):
    res = execute(root, faults={"controls": True})
    assert res["correct"] is True
    assert set(res["controls"]) == set(bank_controls.CONTROLS) | {
        "reference_itself"}
    for name, numbers in res["controls"].items():
        if name == "reference_itself":
            assert verdict(numbers)
        else:
            assert bank_controls.caught_as_named(name, numbers), name


# ------------------------------------------------ faults under the timed path
def after_the_warm_chunks(system, alter):
    """Plant `alter(system)` once, after the chunk that the window's first
    reading follows (the toy runs two warm chunks)."""
    real, calls = system.run, []

    def run(k):
        real(k)
        calls.append(k)
        if len(calls) == 3:
            alter(system)
    system.run = run


def state_unchanged(system):
    real, calls = system.run, []

    def run(k):
        calls.append(k)
        if len(calls) != 4:
            real(k)
        else:  # one chunk counts its steps and returns the state as it was
            system.step_count = system.step_count + k
    system.run = run


def spilled_mail_lost(system):
    def alter(s):  # what the spill region carries, gone
        s.inbox_valid = s.inbox_valid.at[:s.spill_cap].set(False)
    after_the_warm_chunks(system, alter)


def spilled_mail_behind_fresh(system):
    """The spill region read AFTER the emission slots: the delivery sees
    the inbox rolled by the spill's size."""
    core = system._core
    real = core.deliver

    def deliver(dst, mtype, payload, valid, *rest, **kw):
        import jax.numpy as jnp
        k = system.spill_cap
        return real(*(jnp.roll(x, -k, axis=0)
                      for x in (dst, mtype, payload, valid)), *rest, **kw)
    core.deliver = deliver  # read while the step program is traced


def folded_commutatively(system):
    """The account as a reduce behavior: sums applied, the check skipped."""
    import jax.numpy as jnp
    from akka_tpu.batched import Emit
    account = system.behaviors[0]
    assert account.name == "account"

    def receive(state, mailbox, ctx):
        signed = jnp.where(mailbox.types == 1, 1, -1) \
            * mailbox.payload[:, 0].astype(jnp.int32) * mailbox.valid
        count = mailbox.valid.sum().astype(jnp.int32)
        return ({"balance": state["balance"] + signed.sum(),
                 "deposited": state["deposited"]
                 + jnp.where(signed > 0, signed, 0).sum(),
                 "withdrawn": state["withdrawn"]
                 - jnp.where(signed < 0, signed, 0).sum(),
                 "applied": state["applied"] + count}, Emit.none(1, 4))
    account.receive = receive  # read while the step program is traced


def slots_in_reverse(system):
    """Every mailbox folded youngest first."""
    account = system.behaviors[0]
    real = account.receive

    def receive(state, mailbox, ctx):
        return real(state, mailbox._replace(
            types=mailbox.types[::-1], payload=mailbox.payload[::-1],
            valid=mailbox.valid[::-1]), ctx)
    account.receive = receive  # read while the step program is traced


def teller_rule_altered(system):
    system.state["c"] = system.state["c"].at[system.capacity - 7].add(1)


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("fault,caught_by", [
    (state_unchanged, {"messages_unaccounted"}),
    (spilled_mail_lost, {"messages_unaccounted"}),
    (spilled_mail_behind_fresh, {"accounts_wrong", "accounts_wrong_at_open"}),
    (folded_commutatively, {"accounts_wrong", "accounts_wrong_at_open"}),
    (slots_in_reverse, {"accounts_wrong", "accounts_wrong_at_open"}),
    (teller_rule_altered, {"tellers_wrong", "queue_wrong"})])
def test_fault_comes_out_as_not_correct(root, family, fault, caught_by):
    with_family(root, family)
    res = execute(root, faults={"bank_step": fault})
    assert res["correct"] is False
    assert caught_by <= wrong_of(res["compared"]), res["compared"]


def test_rule_stated_twice_has_to_agree(root):
    bench_tiny._shrink(root, "benchmark/traffic/bank-commands.json",
                       {"period": 8})
    with pytest.raises(ValueError, match="period"):
        execute(root)


def test_inbox_rows_stated_have_to_be_the_builders(root):
    bench_tiny._shrink(root, CONFIG, {"inbox_rows": 9999})
    with pytest.raises(ValueError, match="inbox_rows"):
        execute(root)


# --------------------------------------------------------- the rooflines
def test_bank_bytes_against_a_hand_count():
    # 1,048,576 tellers: 16 B of state read, a 24 B message written and read
    # by the enqueue, its 21 B slot written and read by the fold; 131,072
    # accounts: 24 B read and written
    assert rooflines_bank.bank_step_bytes(CONF) == \
        1048576 * (16 + 2 * 24 + 2 * 21) + 131072 * 2 * 24 == 117_440_512
    # the enqueue: a sorted message read, its slot written
    assert rooflines_bank.bank_place_bytes(CONF) == 1048576 * (24 + 21)
    assert CONF["state_bytes_per_account"] == 4 * len(bank.ACCOUNT_COLUMNS)
    assert CONF["state_bytes_per_teller"] == 4 * len(bank.TELLER_COLUMNS)
    assert CONF["slot_bytes"] == 4 + 4 * CONF["payload_width"] + 1


def test_configuration_builds_the_deployment_the_issue_names():
    args = CONF["builder_args"]
    assert (args["n_tellers"], args["n_accounts"]) == (1 << 20, 1 << 17)
    assert (args["mailbox_slots"], args["spill_capacity"]) == (16, 1 << 14)
    assert "delivery_backend" not in args  # what `auto` picks on the chip
    assert (CONF["out_degree"], CONF["payload_width"], CONF["payload_dtype"],
            CONF["host_inbox"]) == (1, 4, "float32", 8)
    assert CONF["inbox_rows"] == 16384 + 131072 + 1048576 + 8 == 1196040
    assert CONF["reduced"] == [] and set(LIMITS.values()) == {0}
    assert len(CONF["source"]) <= 200 and len(CONF["guarantees"]) == 5
    assert (TRAFFIC["period"], TRAFFIC["levels"]) == (16, 8)
    for stated in ("mod period", "1 + ((c_i + t) mod levels)",
                   "2 + ((c_i + t) mod levels)", "one command every step"):
        assert stated in TRAFFIC["what"], stated
