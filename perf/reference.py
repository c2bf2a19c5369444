"""The plain reference: a per-key history model of a replicated register.

It shares no code with ``mochi_tpu``.  It is given what the generator
recorded (every operation of the window, with the time it was issued and the
time its reply came, on one monotonic clock) and what the harness read back
and probed after the window, and it says whether the system gave what the
configuration states:

* every read returned a record that the load or some update of that key
  wrote, byte for byte (tag and checksum), with at least ``quorum`` grants in
  its certificate;
* no read returned a record older than the newest update acknowledged before
  the read was issued.  "Older" is by real time: a read that returns write W
  is stale when some other update of the key was issued after W was
  acknowledged and was itself acknowledged before the read was issued.  An
  update whose reply never came (failed, outcome unknown) may or may not have
  been applied: a read may return it, and it makes nothing stale;
* after the window every key touched reads back the newest acknowledged
  update or one concurrent with it (same rule, the read issued after the
  window), again with at least ``quorum`` grants;
* every bad Write2 of the probe was refused by every replica it was sent to
  and left its key's record unchanged;
* the replicas that run a Byzantine strategy are the ones the configuration
  states, and where it states any: each lied inside the window, the callers
  caught each, and no honest replica was accused by evidence that only a lie
  produces, while every rule above held.

Each number it compares is returned beside its limit; an exact comparison has
the limit 0.
"""

from __future__ import annotations

import bisect
import math
from dataclasses import dataclass, field

# columns of one recorded operation (perf/ycsb.py writes them in this order)
KIND, REC, T_ISSUE, T_DONE, OK, WRITER, SEQ, CRC, GRANTS = range(9)
READ, UPDATE = 0, 1


@dataclass
class Check:
    """One number compared, with its limit; ``at_least`` for a lower limit."""

    name: str
    value: float
    limit: float
    at_least: bool = False

    @property
    def ok(self) -> bool:
        return self.value >= self.limit if self.at_least else self.value <= self.limit

    def line(self) -> str:
        rel = ">=" if self.at_least else "<="
        return (f"check {self.name}: {self.value} (limit {rel} {self.limit}) "
                f"{'ok' if self.ok else 'FAILED'}")


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q`` percent
    of the sample at or below it.  A failed operation is ``math.inf``, so it
    counts as over any limit."""
    if not values:
        return math.nan
    data = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(data)))
    return data[rank - 1]


@dataclass
class KeyHistory:
    """Writes of one key.  ``acked`` holds (t_ack, t_issue) of every
    acknowledged update, sorted by t_ack, with the running maximum of t_issue,
    so "the latest issue time among updates acknowledged before t" is one
    bisection."""

    writes: dict = field(default_factory=dict)  # (writer, seq) -> (t_issue, t_ack, crc)
    _ack_times: list = field(default_factory=list)
    _max_issue: list = field(default_factory=list)

    def add_write(self, tag, t_issue, t_ack, crc) -> None:
        self.writes[tag] = (t_issue, t_ack, crc)

    def seal(self) -> None:
        acked = sorted((w[1], w[0]) for w in self.writes.values() if w[1] < math.inf)
        self._ack_times = [a for a, _ in acked]
        running, best = [], -math.inf
        for _, issued in acked:
            best = max(best, issued)
            running.append(best)
        self._max_issue = running

    def newest_issue_acked_before(self, t: float) -> float:
        i = bisect.bisect_left(self._ack_times, t)
        return self._max_issue[i - 1] if i else -math.inf

    def judge_read(self, tag, crc, t_issue) -> str:
        """'ok', 'unknown' (no such write of this key, or other bytes), or
        'stale'."""
        w = self.writes.get(tag)
        if w is None or w[2] != crc:
            return "unknown"
        if w[1] < self.newest_issue_acked_before(t_issue):
            return "stale"
        return "ok"


def build_histories(ops, load_writer: int, value_crc) -> dict:
    """Per-record histories from the recorded operations.  ``value_crc(writer,
    seq)`` recomputes the checksum of the record an operation wrote, from the
    seed and not from what the generator says it sent."""
    hist: dict = {}
    for op in ops:
        if op[KIND] != UPDATE:
            continue
        h = hist.setdefault(op[REC], KeyHistory())
        t_ack = op[T_DONE] if op[OK] else math.inf
        h.add_write((op[WRITER], op[SEQ]), op[T_ISSUE], t_ack, value_crc(op[WRITER], op[SEQ]))
    for op in ops:
        hist.setdefault(op[REC], KeyHistory())
    for rec, h in hist.items():
        # the load wrote every record before the window opened
        h.add_write((load_writer, rec), -math.inf, -math.inf, value_crc(load_writer, rec))
        h.seal()
    return hist


def check_window(ops, hist: dict, quorum: int) -> list:
    """The window's reads against the histories."""
    unknown = stale = short = 0
    for op in ops:
        if op[KIND] != READ or not op[OK]:
            continue
        verdict = hist[op[REC]].judge_read((op[WRITER], op[SEQ]), op[CRC], op[T_ISSUE])
        unknown += verdict == "unknown"
        stale += verdict == "stale"
        short += op[GRANTS] < quorum
    return [
        Check("window_reads_of_no_known_write", unknown, 0),
        Check("window_stale_reads", stale, 0),
        Check("window_reads_under_quorum_grants", short, 0),
    ]


def check_readback(readback, hist: dict, quorum: int) -> list:
    """``readback``: {record: (writer, seq, crc, grants, t_issue)} for every
    record the window touched, read after it closed (writer -1: no tag)."""
    missing = wrong = short = 0
    for rec, h in hist.items():
        got = readback.get(rec)
        if got is None:
            missing += 1
            continue
        writer, seq, crc, grants, t_issue = got
        wrong += h.judge_read((writer, seq), crc, t_issue) != "ok"
        short += grants < quorum
    return [
        Check("readback_missing", missing, 0),
        Check("readback_not_newest_acknowledged", wrong, 0),
        Check("readback_under_quorum_grants", short, 0),
    ]


def check_probe(probe: list) -> list:
    """``probe``: one dict per bad Write2 — ``kind``, ``sent`` (replicas it
    went to), ``accepted`` (replicas that answered it as a commit),
    ``unchanged`` (the key read back the same record afterwards)."""
    accepted = sum(p["accepted"] for p in probe)
    changed = sum(1 for p in probe if not p["unchanged"])
    return [
        Check("bad_write2_sent", sum(1 for p in probe if p["sent"] > 0), len(probe), at_least=True),
        Check("bad_write2_accepted_by_replicas", accepted, 0),
        Check("bad_write2_changed_a_record", changed, 0),
    ]


def summarize(ops, seconds: float, t_end: float) -> dict:
    """End-to-end numbers of the window, from the generator's side: every
    operation issued in the window counts for the tails (a failed one as
    infinite), and an operation counts for the rate when it completed inside
    the window and was answered."""
    lat = {READ: [], UPDATE: []}
    done_ok = failed = 0
    for op in ops:
        ms = (op[T_DONE] - op[T_ISSUE]) * 1e3 if op[OK] else math.inf
        lat[op[KIND]].append(ms)
        failed += not op[OK]
        done_ok += bool(op[OK]) and op[T_DONE] <= t_end
    return {
        "attempted": len(ops),
        "failed": failed,
        "ops_s": done_ok / seconds,
        "latency_ms": lat,
    }


def by_second(ops, t_start: float, seconds: float) -> dict:
    """The window second by second: operations answered in each second, and
    the median latency of the reads issued in it (ms; None where none was).
    A level that sets in with a fault shows here, where the window's own
    numbers show only the mix."""
    n = int(math.ceil(seconds))
    answered = [0] * n
    reads = [[] for _ in range(n)]
    for op in ops:
        done = op[T_DONE] - t_start
        if op[OK] and 0 <= done <= seconds:  # as the rate counts it
            answered[min(int(done), n - 1)] += 1
        issued = int(op[T_ISSUE] - t_start)
        if op[KIND] == READ and op[OK] and 0 <= issued < n:
            reads[issued].append((op[T_DONE] - op[T_ISSUE]) * 1e3)
    return {"answered": answered,
            "read_p50_ms": [round(percentile(r, 50), 2) if r else None for r in reads]}


# what a configuration file states -> what a replica's /status reports for it
ENGINE_REPORTED = {"wal": "durable", "paged": "paged"}


def check_deployment(config: dict, replicas: dict) -> list:
    """The deployment the configuration STATES against what the replicas
    report they run: ``replicas`` has the sorted distinct values over all
    replicas of the storage engine, the fsync policy and admission control.
    Each number is how many distinct values differ from the stated one; the
    last, how many replicas run another strategy than the stated one (none,
    where the configuration states no ``byzantine`` map)."""
    want = {
        "storage_engines": ENGINE_REPORTED.get(config["storage_engine"], config["storage_engine"]),
        "fsync_policies": config["wal_fsync"],
        "admission": str(config["admission"] == "on"),
    }
    return [
        Check(f"replicas_reporting_other_{key}", sum(1 for v in replicas[key] if v != stated), 0)
        for key, stated in want.items()
    ] + [Check("replicas_whose_strategy_differs_from_what_the_configuration_states",
               strategy_differs(config.get("byzantine") or {}, replicas.get("byzantine") or {}), 0)]


def strategy_differs(stated: dict, reported: dict) -> int:
    """``stated``: {server id: strategy}, the configuration's ``byzantine`` map
    (absent: every member is honest).  ``reported``: {server id: what that
    replica says of itself}, with ``strategy`` (None where it runs none, True
    where it runs one and does not name it) among its keys.  A stated member
    that runs no strategy, a replica that runs one and is not stated, and a
    named strategy that is not the stated one each count; a replica that says
    nothing of itself runs none."""
    differ = 0
    for sid in set(stated) | set(reported):
        runs = (reported.get(sid) or {}).get("strategy")
        want = stated.get(sid)
        differ += (want is None) != (runs is None) or (isinstance(runs, str) and runs != want)
    return differ


# the marks a caller's SDK keeps against a replica (``suspect.<kind>.<sid>``)
# that only a lie produces: a grant whose signature or transaction hash is
# wrong, an agreeing answer whose certificate does not build.  An honest
# replica earns ``grant-conflict`` and ``tally-outvoted`` under contention on
# the zipfian's head (7-25 of each a replica a window with five honest
# members), ``no-response`` and the straggler time-outs by being slow.
LIE_KINDS = ("bad-grant", "bad-certificate")
# the kinds by which callers catch each strategy's lies; a strategy that is
# not here is caught by a mark of any kind
CAUGHT_AS = {"forge-cert": ("bad-grant",)}


def acts_gained(replicas_before: dict, replicas_after: dict, sid: str):
    """How often replica ``sid``'s own strategy acted between two looks
    (``cluster.replica_counters`` at either end), by its own count: responses
    it changed plus requests it swallowed.  None where it reports no strategy
    at the second look; a replica that was started again in between (its
    counters began anew) reports what it counted since."""
    def acts(replicas):
        own = (replicas.get("byzantine") or {}).get(sid) or {}
        if own.get("strategy") is None:
            return None
        return int(own.get("mutated_responses", 0)) + int(own.get("dropped_requests", 0))

    a0, a1 = acts(replicas_before) or 0, acts(replicas_after)
    if a1 is None:
        return None
    return a1 - a0 if a1 >= a0 else a1


def _proofs(replicas: dict) -> dict:
    """{accused server id: count}: what the replicas hold against their peers
    as PROOF, summed over the replicas that report it: ``equivocations``, two
    validly signed grants of one slot to two transactions.  Their
    ``bad_grants`` are left out: a grant whose signature does not verify is
    counted against the signer it CLAIMS, whoever carried the certificate
    (``replica.py``: "evidence about the CARRIER of the certificate, not proof
    against sid"), so the probe's altered Write2 earns an honest signer one in
    every cell, and a member's tampered sync answer earns the honest signers
    of the entry one each."""
    out: dict = {}
    for own in (replicas.get("byzantine") or {}).values():
        for sid, count in ((own or {}).get("equivocations") or {}).items():
            out[sid] = out.get(sid, 0) + int(count)
    return out


def check_byzantine(stated: dict, replicas_before: dict, replicas_after: dict,
                    sdk_gained: dict) -> list:
    """A cell whose configuration states Byzantine members (``stated``: {server
    id: strategy}).  Each member acted inside the window, by its own count
    (``replicas_*``: ``cluster.replica_counters`` at either end); the callers
    caught each one lying at least once (``sdk_gained``: what the SDK's counters
    gained over the window, summed over the callers); and nothing that only a
    lie of the accused produces (the callers' ``LIE_KINDS``, the replicas'
    ``_proofs``) was laid at an honest replica's door.  Every other check
    holds the guarantees, with the members answering throughout."""
    idle = sum(1 for sid in stated if not acts_gained(replicas_before, replicas_after, sid))
    marks: dict = {}  # (kind, server id) -> marks the callers gained
    for name, n in sdk_gained.items():
        if name.startswith("suspect."):
            _, kind, sid = name.split(".", 2)
            marks[kind, sid] = marks.get((kind, sid), 0) + n

    def caught(member, strategy):  # marks of the kinds that catch this strategy's lies
        return sum(n for (kind, sid), n in marks.items()
                   if sid == member and kind in CAUGHT_AS.get(strategy, (kind,)))

    accused = {sid for (kind, sid), n in marks.items() if n > 0 and kind in LIE_KINDS}
    held0, held1 = _proofs(replicas_before), _proofs(replicas_after)
    accused |= {sid for sid, n in held1.items() if n > held0.get(sid, 0)}
    return [
        Check("stated_members_that_never_acted_in_the_window", idle, 0),
        Check("lies_the_callers_caught", min((caught(*m) for m in stated.items()), default=0), 1,
              at_least=True),
        Check("honest_replicas_accused_by_typed_evidence", len(accused - set(stated)), 0),
    ]


def check_recovery(records: list) -> list:
    """The fault schedule's records (``perf/schedule.py``): a replica that was
    started again replayed its own log with nothing convicted, and holds at
    READY at least as many live keys as just before it was killed (the load
    wrote every key before the window, so the count was not rising).  The WAL
    engine counts them as replay entries too (every key it held is in its
    snapshot or in its log after it); the paged engine maps its pages in
    without replaying them or making them resident and counts their
    ``pages.live_entries``: the largest of the three counts is compared."""
    held: dict = {}
    convicted = short = restarted = 0
    for rec in records:
        if rec["before"]["replica"] is not None:
            held[rec["server_id"]] = rec["before"]["replica"]["store"]["keys_live"]
        after = rec["after"]["replica"]
        if rec["before"]["replica"] is None and after is not None:  # it came back
            restarted += 1
            replay = after["storage"].get("replay") or {}
            convicted += int(replay.get("convicted", 0))
            back = max(int(replay.get("entries", 0)), int(after["store"]["keys_live"]),
                       int((after["storage"].get("pages") or {}).get("live_entries", 0)))
            short += back < held.get(rec["server_id"], math.inf)
        elif rec["before"]["replica"] is None:
            short += 1  # started again and not answering
    return [
        Check("replicas_restarted", restarted, 1, at_least=True),
        Check("replay_entries_convicted", convicted, 0),
        Check("replicas_back_with_fewer_keys_than_held_before_the_kill", short, 0),
    ]


def check_direct(direct: dict, hist: dict, records: list, slack_s: float, quorum: int) -> list:
    """``direct``: {server id: {record: (writer, seq, crc, grants) or None}},
    every record that replica owns and the window updated, read from THAT
    replica alone after the window.  A restarted replica answers each with a
    write of that record that the history knows, under a quorum certificate,
    and no older than the newest update acknowledged ``slack_s`` seconds or
    more before it was killed: a Write2 goes to the whole replica set, the
    caller is answered by the first quorum, and the last replica's write to
    its log may trail that answer, so an update acknowledged inside the slack
    may have died with the process.  What was committed while it was down it
    learns only from a later write or a nudge (the product starts it without
    ``--resync-on-boot``): those records are counted (``behind``), not compared.
    Returns the checks, and the counts for the commentary and the readers.
    ``records`` give the time of each kill, on the generators' clock."""
    killed_at = {r["server_id"]: r["t_mono"] for r in records if r["after"]["replica"] is None
                 and r["before"]["replica"] is not None}
    missing = unknown = short = lost = behind = asked = 0
    for sid, answers in direct.items():
        horizon = killed_at.get(sid, math.inf) - slack_s
        for rec, got in answers.items():
            asked += 1
            if got is None:
                missing += 1
                continue
            writer, seq, crc, grants = got
            h = hist[rec]
            w = h.writes.get((writer, seq))
            if w is None or w[2] != crc:
                unknown += 1
                continue
            short += grants < quorum
            lost += w[1] < h.newest_issue_acked_before(horizon)
            behind += w[1] < h.newest_issue_acked_before(math.inf)
    return [
        Check("direct_reads_sent", asked, 1, at_least=True),
        Check("direct_reads_unanswered_or_empty", missing, 0),
        Check("direct_reads_of_no_known_write", unknown, 0),
        Check("direct_reads_under_quorum_grants", short, 0),
        Check("direct_reads_older_than_acknowledged_before_the_kill", lost, 0),
    ], {"asked": asked, "behind": behind}
