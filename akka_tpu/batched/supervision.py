"""In-graph vectorized supervision: 'let it crash' inside the jitted step.

The host-mediated error lane (step.py fault_* helpers: a sticky `_failed`
flag polled via any_failed(), resolved by restart_rows/clear_failed) costs a
device sync per recovery — exactly the host round-trip the north star
forbids on the hot path. This module compiles the supervisor into the step
itself: each BatchedBehavior may carry a LaneSupervisor, and StepCore.update
applies its directive as masked lane ops in the SAME jitted pass that
detects the failure (CAF's OpenCL actors, PAPERS.md arXiv:1709.07781: fault
handling must live in the data-parallel kernel, not the coordinator).

Reference parity (actor/supervision.py, FaultHandling.scala), translated to
lane form with a STEP-COUNT time base instead of wall clock:

  RESUME    clear `_failed`, keep state. The failing receive's update was
            already discarded by the step (handleInvokeFailure parity), so
            resume == "pretend the poison message never happened".
  RESTART   re-initialize the lane's state columns (zeros / re-arm values /
            per-behavior restart_state overrides) and bump its device
            generation `_gen` — messages arriving while the lane is down
            dead-letter instead of reaching the next incarnation (path-uid
            parity with the host generation counter, core.py). Restart
            frequency is governed by max_nr_of_retries within a
            within_steps window, and each retry backs the lane off
            exponentially (min_backoff_steps << retries, capped at
            max_backoff_steps) — pattern/backoff.py's BackoffSupervisor
            with steps for seconds. During backoff the lane stays
            suspended and its mail is counted as dead letters.
  STOP      the lane dies (alive=False), `_failed` clears so a dead row
            stops re-reporting, `_gen` bumps. Retries-exhausted RESTART
            degrades to STOP (OneForOneStrategy.processFailure parity).
  ESCALATE  the lane stays suspended and the `_escalated` flag raises; the
            host checks any_escalated() when IT chooses (one device
            scalar) — no forced sync on the step path.

Everything here is branch-free masked arithmetic over [n_lanes] columns:
one supervision pass costs a handful of element-wise ops regardless of how
many lanes failed, and zero-failure steps pay the same (a CPU-era budget
of <=5% of step time; not measured on the chip).

See docs/SUPERVISION.md for the full semantics and divergences.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..actor.supervision import Directive


def _colshape(mask, like):
    """Broadcast a [n] lane mask against a [n, ...] state column."""
    return jnp.reshape(mask, mask.shape + (1,) * (like.ndim - 1))

# Directive -> lane code (lax-friendly int32; order matches Directive docs)
LANE_RESUME, LANE_RESTART, LANE_STOP, LANE_ESCALATE = 0, 1, 2, 3
_LANE_CODE = {Directive.RESUME: LANE_RESUME, Directive.RESTART: LANE_RESTART,
              Directive.STOP: LANE_STOP, Directive.ESCALATE: LANE_ESCALATE}

# aggregate counter slots (the [N_COUNTERS] int32 vector in the step carry)
(FAILED, RESUMED, RESTARTED, STOPPED, ESCALATED, DEAD_LETTERS) = range(6)
N_COUNTERS = 6
COUNTER_NAMES = ("failed", "resumed", "restarted", "stopped", "escalated",
                 "dead_letters")

# per-lane bookkeeping columns, injected into the state schema by the
# system when any behavior carries a supervisor. `_failed` is the existing
# error lane; the rest are supervision state and SURVIVE an in-graph
# restart (only behavior columns are re-initialized).
SUP_COLUMNS: Dict[str, Any] = {
    "_failed": ((), jnp.bool_),
    "_retries": ((), jnp.int32),       # restarts inside the current window
    "_window_start": ((), jnp.int32),  # step the window opened
    "_restart_at": ((), jnp.int32),    # pending backoff restart (-1 = none)
    "_escalated": ((), jnp.bool_),
    "_gen": ((), jnp.int32),           # device-side incarnation counter
}
_RESERVED = frozenset(SUP_COLUMNS)


def reserved_fill(col: str) -> int:
    """Re-arm value a reserved column takes on init/reset (everything else
    zeros). Shared by core.py, sharded.py and the fault_* helpers so the
    special cases live in one place."""
    return -1 if col in ("_become", "_restart_at") else 0


@dataclass(frozen=True)
class LaneSupervisor:
    """Per-behavior supervision spec, applied in-graph to every lane running
    the behavior (OneForOne semantics: a failure touches only its own lane).

    directive: what a fresh failure resolves to (actor/supervision.py
    Directive). max_nr_of_retries / within_steps: RESTART permission
    accounting (ChildRestartStats.requestRestartPermission with steps for
    seconds; -1 retries = unlimited, within_steps=0 = one unbounded
    window; max_nr_of_retries=0 = never restart, i.e. STOP).
    min/max_backoff_steps: exponential restart delay in steps
    (min << retries, capped; 0 min = restart in the failing step's own
    pass). restart_state: scalar column overrides applied on in-graph
    restart (columns default to zeros / re-arm values — the batched
    analogue of re-running the props constructor)."""

    directive: Directive = Directive.RESTART
    max_nr_of_retries: int = -1
    within_steps: int = 0
    min_backoff_steps: int = 0
    max_backoff_steps: int = 0
    restart_state: Dict[str, Any] = field(default_factory=dict)

    def __post_init__(self):
        if self.directive not in _LANE_CODE:
            raise ValueError(f"unknown directive {self.directive!r}")
        if self.min_backoff_steps < 0 or self.max_backoff_steps < 0:
            raise ValueError("backoff steps must be >= 0")


class SupervisionTables:
    """Trace-time constants for the supervision pass: one small
    [n_behaviors] row per parameter, gathered by behavior_id into lane
    columns inside the jit. Built once per StepCore."""

    def __init__(self, behaviors: Sequence[Any]):
        sups = [getattr(b, "supervisor", None) for b in behaviors]
        self.active = any(s is not None for s in sups)
        self._restart_state = [dict(s.restart_state) if s else {}
                               for s in sups]
        self._fill_cache: Dict[str, np.ndarray] = {}
        if not self.active:
            return
        default = LaneSupervisor()  # placeholder row for unsupervised ids

        def row(fn, dtype=jnp.int32):
            return jnp.asarray([fn(s if s is not None else default)
                                for s in sups], dtype)

        self.enabled = jnp.asarray([s is not None for s in sups], jnp.bool_)
        self.directive = row(lambda s: _LANE_CODE[s.directive])
        self.max_retries = row(lambda s: s.max_nr_of_retries)
        self.window = row(lambda s: s.within_steps)
        self.min_backoff = row(lambda s: s.min_backoff_steps)
        self.max_backoff = row(lambda s: s.max_backoff_steps)

    def fill_row(self, col: str, dtype) -> jax.Array:
        """[n_behaviors] restart fill values for one state column: the
        reserved re-arm value / zero, unless the behavior's restart_state
        overrides it (scalar overrides only). The cache holds NUMPY rows:
        a jnp array materialized during one jit trace is a tracer there,
        and caching it would leak it into the next trace."""
        if col not in self._fill_cache:
            base = reserved_fill(col)
            vals = [rs.get(col, base) for rs in self._restart_state]
            self._fill_cache[col] = np.asarray(vals)
        return jnp.asarray(self._fill_cache[col], dtype)


def apply_supervision(tables: SupervisionTables, state: Dict[str, jax.Array],
                      behavior_id: jax.Array, alive: jax.Array,
                      old_failed: jax.Array, delivered_count: jax.Array,
                      step: jax.Array):
    """The vectorized supervisor: one column-wise pass right after the
    behavior switch, inside the same jitted step that detected the
    failures. Returns (new_state, new_alive, counts_delta[N_COUNTERS]).

    `state` is the post-switch state (failing lanes already hold their
    pre-failure columns plus a sticky `_failed`); `old_failed` is the flag
    BEFORE the switch, so `failed & ~old_failed` isolates this step's
    fresh failures. `delivered_count` ([n] int32, messages addressed to
    each lane this step) prices dead letters for mail that arrived at a
    lane that was already down when the step began.
    """
    i32 = jnp.int32
    enabled = tables.enabled[behavior_id]

    def resolve(st):
        code = tables.directive[behavior_id]
        failed = st["_failed"]
        fresh = failed & ~old_failed & alive

        counts = jnp.zeros((N_COUNTERS,), i32)
        counts = counts.at[FAILED].add(jnp.sum(fresh.astype(i32)))
        # mail addressed to a supervised lane that was suspended or dead at
        # step start: the incarnation it was sent to is gone (or not yet
        # restarted) — dead-letter it, don't deliver to the next occupant
        dead_dst = enabled & (old_failed | ~alive)
        counts = counts.at[DEAD_LETTERS].add(
            jnp.sum(jnp.where(dead_dst, delivered_count, 0)).astype(i32))

        act = fresh & enabled
        resume = act & (code == LANE_RESUME)
        want_restart = act & (code == LANE_RESTART)
        escalate = act & (code == LANE_ESCALATE)

        # -- restart permission: retries within a step-count window --------
        win = tables.window[behavior_id]
        expired = (win > 0) & ((step - st["_window_start"]) >= win)
        eff_retries = jnp.where(want_restart & expired, 0, st["_retries"])
        maxr = tables.max_retries[behavior_id]
        permitted = (maxr < 0) | (eff_retries < maxr)

        # -- exponential backoff in steps: min << retries, capped ----------
        minb = tables.min_backoff[behavior_id]
        cap = jnp.maximum(tables.max_backoff[behavior_id], minb)
        raw = minb << jnp.minimum(eff_retries, 24)
        delay = jnp.where(minb > 0,
                          jnp.where(raw < minb, cap,  # int32 wrap -> cap
                                    jnp.minimum(raw, cap)), 0)

        scheduled = want_restart & permitted
        restart_now = scheduled & (delay == 0)
        restart_later = scheduled & (delay > 0)
        exhausted = want_restart & ~permitted
        # a backoff restart coming due: the lane failed in an earlier step
        # and its delay has elapsed (the lane sat suspended through the
        # switch above, so it resumes processing NEXT step)
        due = failed & ~fresh & alive & enabled & \
            (st["_restart_at"] >= 0) & (step >= st["_restart_at"])

        do_restart = restart_now | due
        stop = (act & (code == LANE_STOP)) | exhausted

        # -- restart: re-initialize the lane's behavior columns ------------
        # gated on any restart actually firing: this loop is the only part
        # of the pass that scales with the number of BEHAVIOR columns
        user_cols = {c: v for c, v in st.items() if c not in _RESERVED}
        if user_cols:
            def fill_cols(cols):
                out = {}
                for col, v in cols.items():
                    fill = tables.fill_row(col, v.dtype)[behavior_id]
                    fill = jnp.broadcast_to(_colshape(fill, v), v.shape)
                    out[col] = jnp.where(_colshape(do_restart, v), fill, v)
                return out

            st.update(jax.lax.cond(jnp.any(do_restart), fill_cols,
                                   lambda cols: cols, user_cols))

        # -- bookkeeping ---------------------------------------------------
        st["_window_start"] = jnp.where(scheduled & (eff_retries == 0), step,
                                        st["_window_start"])
        st["_retries"] = jnp.where(scheduled, eff_retries + 1,
                                   st["_retries"])
        st["_restart_at"] = jnp.where(
            restart_later, step + delay,
            jnp.where(due, -1, st["_restart_at"]))
        st["_escalated"] = st["_escalated"] | escalate
        st["_gen"] = st["_gen"] + (do_restart | stop).astype(i32)
        st["_failed"] = failed & ~(resume | do_restart | stop)
        new_alive = alive & ~stop

        counts = counts.at[RESUMED].add(jnp.sum(resume.astype(i32)))
        counts = counts.at[RESTARTED].add(jnp.sum(do_restart.astype(i32)))
        counts = counts.at[STOPPED].add(jnp.sum(stop.astype(i32)))
        counts = counts.at[ESCALATED].add(jnp.sum(escalate.astype(i32)))
        return st, new_alive, counts

    # the whole pass is identity unless some lane is failed (covers fresh
    # failures, suspended lanes, pending backoff restarts — the sticky flag
    # holds through all of them) or mail arrived for a dead supervised lane
    # (device-STOPped rows keep dead-lettering). Quiet steps pay only this
    # predicate — a couple of reductions — instead of the ~25 bookkeeping
    # ops of the full pass
    relevant = jnp.any(state["_failed"]) | jnp.any(
        enabled & ~alive & (delivered_count > 0))
    return jax.lax.cond(
        relevant, resolve,
        lambda st: (st, alive, jnp.zeros((N_COUNTERS,), i32)),
        dict(state))


def counts_dict(vec) -> Dict[str, int]:
    """[N_COUNTERS] vector -> named dict (host side)."""
    import numpy as np
    arr = np.asarray(jax.device_get(vec)).reshape(-1, N_COUNTERS).sum(0)
    return {name: int(arr[i]) for i, name in enumerate(COUNTER_NAMES)}


# --------------------------------------------------------------------------
# Host-attention word
#
# The depth-k bridge pump (batched/bridge.py) and the pipelined drivers
# drain their in-flight programs by fetching ONE tiny int32 vector per
# round instead of `block_until_ready` plus separate wide device_gets of
# `_failed`, `_escalated` and the promise-latch column. The word is a
# NON-donated output of the jitted step, so `device_get` on its handle
# doubles as the sync point for that step's whole program.

ATT_WORDS = 6
(ATT_FLAGS, ATT_DROPPED, ATT_DEAD_LETTERS, ATT_STEP,
 ATT_EXCH_DROPPED, ATT_PROGRESS) = range(ATT_WORDS)

# ATT_FLAGS bit layout
ATT_FAILED_BIT = 1     # some lane holds `_failed` (feeds _handle_failures)
ATT_ESCALATED_BIT = 2  # some lane holds `_escalated` (host must resolve)
ATT_LATCH_BIT = 4      # some promise row latched a reply (bridge asks)

# Word semantics when the word is packed PER SHARD ([n_shards, ATT_WORDS],
# the ShardedBatchedSystem layout): ATT_DROPPED / ATT_DEAD_LETTERS /
# ATT_EXCH_DROPPED hold the packing shard's LOCAL cumulative counts (their
# sum across rows is the global total, which is what decode_attention
# reports), and ATT_PROGRESS is the shard's own dispatched-step counter —
# the per-shard heartbeat lane. A live shard's progress word advances on
# every drained program; a preempted or hung shard's lane freezes at its
# last completed step, which is exactly the signal the MeshSentinel's
# phi-accrual detectors consume (batched/sentinel.py). On a single device
# ATT_PROGRESS mirrors ATT_STEP and ATT_EXCH_DROPPED is 0 (no exchange).


def attention_flags(state: Dict[str, jax.Array],
                    latch_col: Optional[str] = None) -> jax.Array:
    """[()] int32 flag word over the state columns (traced in-graph).
    Absent columns contribute a trace-time zero — unsupervised systems
    pay nothing for the bits they can never raise."""
    i32 = jnp.int32
    flags = jnp.asarray(0, i32)
    if "_failed" in state:
        flags = flags | jnp.any(state["_failed"]).astype(i32) * ATT_FAILED_BIT
    if "_escalated" in state:
        flags = flags | (jnp.any(state["_escalated"]).astype(i32)
                         * ATT_ESCALATED_BIT)
    if latch_col is not None and latch_col in state:
        flags = flags | (jnp.any(state[latch_col] != 0).astype(i32)
                         * ATT_LATCH_BIT)
    return flags


def pack_attention(state: Dict[str, jax.Array], mail_dropped, sup_counts,
                   step_count, latch_col: Optional[str] = None,
                   exch_dropped=None, progress=None) -> jax.Array:
    """[ATT_WORDS] int32 attention word for one step (traced in-graph).
    `mail_dropped` / `sup_counts` may be scalars or per-shard blocks —
    both reduce to totals here, so single-device and shard_map callers
    share the packing. `exch_dropped` is the caller's exchange-overflow
    aggregate (sharded: the per-pair drop counter block; absent on a
    single device); `progress` overrides the heartbeat lane (defaults to
    step_count — a shard_map caller inside a sharded step passes its own
    counter, which is the same value but packed per shard)."""
    i32 = jnp.int32
    dropped = jnp.sum(jnp.asarray(mail_dropped)).astype(i32)
    dead = jnp.reshape(jnp.asarray(sup_counts),
                       (-1, N_COUNTERS))[:, DEAD_LETTERS].sum().astype(i32)
    step = jnp.asarray(step_count).astype(i32)
    exch = (jnp.sum(jnp.asarray(exch_dropped)).astype(i32)
            if exch_dropped is not None else jnp.asarray(0, i32))
    prog = (jnp.asarray(progress).astype(i32).reshape(())
            if progress is not None else step)
    return jnp.stack([attention_flags(state, latch_col), dropped, dead,
                      step, exch, prog])


def decode_attention(word) -> Dict[str, Any]:
    """Host-side decode of attention word(s): [ATT_WORDS] or, sharded,
    [n_shards, ATT_WORDS]. Flags OR across shards, counters sum, step
    takes the max. Per-shard counter columns are also surfaced raw
    (`*_per_shard` numpy rows, one entry per word) so the sentinel and
    read_attention() callers can tell WHICH shard is overflowing or
    stalled without another device round-trip. Legacy 4-word arrays
    (pre-progress-lane snapshots) decode with the new lanes zeroed."""
    import numpy as np
    a = np.asarray(jax.device_get(word), np.int64)
    if a.size % ATT_WORDS != 0 and a.size % 4 == 0:
        # pre-v3 word layout: [flags, dropped, dead_letters, step]
        legacy = a.reshape(-1, 4)
        a = np.zeros((legacy.shape[0], ATT_WORDS), np.int64)
        a[:, :4] = legacy
        a[:, ATT_PROGRESS] = legacy[:, ATT_STEP]
    else:
        a = a.reshape(-1, ATT_WORDS)
    flags = int(np.bitwise_or.reduce(a[:, ATT_FLAGS])) if a.size else 0
    return {
        "flags": flags,
        "any_failed": bool(flags & ATT_FAILED_BIT),
        "any_escalated": bool(flags & ATT_ESCALATED_BIT),
        "any_latched": bool(flags & ATT_LATCH_BIT),
        "mail_dropped": int(a[:, ATT_DROPPED].sum()),
        "dead_letters": int(a[:, ATT_DEAD_LETTERS].sum()),
        "step": int(a[:, ATT_STEP].max()) if a.size else 0,
        "exchange_dropped": int(a[:, ATT_EXCH_DROPPED].sum()),
        "mail_dropped_per_shard": a[:, ATT_DROPPED].copy(),
        "dropped_per_shard": a[:, ATT_EXCH_DROPPED].copy(),
        "progress_per_shard": a[:, ATT_PROGRESS].copy(),
    }
