"""The fault schedule of a cell: what its traffic file states under ``faults``,
checked, bound to the run's seed, and executed inside the window.

    "faults": [{"at_s": 5.0, "do": "kill_replica", "replica": "seeded"},
               {"at_s": 10.0, "do": "restart_replica", "replica": "same"}]

``at_s`` counts from the window's start.  ``replica`` is ``"seeded"`` (drawn
from ``--seed`` among the replicas that are up) or ``"same"`` (the replica of
the event before).
A verb is a file, ``perf/faults/<verb>.py``, as a per-layer metric is a file in
``perf/layer_metrics/``::

    KILLS = True | RESTARTS = True      # what the verb does to its replica
    END_TO_END = "recover_s"            # optional: the end-to-end metric that
                                        # sums the ``seconds`` of its events
    async def run(pc, event, state) -> dict   # calls the one ProcessCluster
                                              # method; returns what it timed

Nothing here names a verb.  A traffic file with no ``faults`` key never gets
here: ``perf/run.py`` starts no task for it.
"""

from __future__ import annotations

import asyncio
import os
import random
import time

import layer_reader

REPLICA_WORDS = ("seeded", "same")


class ScheduleError(ValueError):
    """The schedule cannot be run as stated."""


def load_verb(faults_dir: str, verb: str):
    path = os.path.join(faults_dir, f"{verb}.py")
    if not isinstance(verb, str) or not verb or os.sep in verb or not os.path.isfile(path):
        have = sorted(n[:-3] for n in os.listdir(faults_dir) if n.endswith(".py")) \
            if os.path.isdir(faults_dir) else []
        raise ScheduleError(f"unknown fault verb {verb!r}: no {path} (has {have})")
    return layer_reader.load(path, "fault_verb_")


def validate(faults, faults_dir: str) -> list:
    """What a traffic file alone can be held to: every verb has its file, the
    times are numbers in order, a replica is one of the two words, a restart
    follows a kill of the same replica, and every kill is undone (the checks
    after the window need all n replicas answering).  Returns the verbs'
    modules, one an event."""
    if not isinstance(faults, list) or not faults:
        raise ScheduleError("'faults' is a non-empty list of events")
    verbs, last_at, dead, previous = [], 0.0, [], None
    for i, ev in enumerate(faults):
        if not isinstance(ev, dict) or not {"at_s", "do", "replica"} <= set(ev):
            raise ScheduleError(f"fault {i}: an event has 'at_s', 'do' and 'replica'")
        mod = load_verb(faults_dir, ev["do"])
        at = ev["at_s"]
        if isinstance(at, bool) or not isinstance(at, (int, float)) or at < 0 or at < last_at:
            raise ScheduleError(f"fault {i}: 'at_s' {at!r} is not a time at or after the event before")
        last_at = float(at)
        who = ev["replica"]
        if who not in REPLICA_WORDS:
            raise ScheduleError(f"fault {i}: 'replica' is 'seeded' or 'same', not {who!r}")
        if who == "same":
            if previous is None:
                raise ScheduleError(f"fault {i}: 'same' with no event before it")
            who = previous
        else:
            who = i  # each 'seeded' is a draw of its own
        previous = who
        if getattr(mod, "RESTARTS", False):
            if who not in dead:
                raise ScheduleError(f"fault {i}: {ev['do']} of a replica that nothing killed")
            dead.remove(who)
        if getattr(mod, "KILLS", False):
            if who in dead:
                raise ScheduleError(f"fault {i}: {ev['do']} of a replica that is already down")
            dead.append(who)
        verbs.append(mod)
    if dead:
        raise ScheduleError(f"{len(dead)} replica(s) killed and never restarted: the checks after "
                            "the window need every replica answering")
    return verbs


def bind(faults: list, verbs: list, seed: int, seconds: float, n: int, f: int,
         process_of: dict, members=()) -> list:
    """The schedule of THIS run: each event with its replica's id, refused
    where the cell cannot carry it.  ``process_of``: server id -> the index
    of the process that hosts it (a kill takes the whole process).
    ``members``: the replicas the configuration states as Byzantine; one of
    them is faulty whether it is up or down, so it counts against ``f``
    beside the replicas that are down."""
    rng = random.Random(f"faults:{seed}")
    hosted = {}
    for sid, proc in process_of.items():
        hosted.setdefault(proc, []).append(sid)
    events, dead, previous = [], set(), None
    for i, (ev, mod) in enumerate(zip(faults, verbs)):
        if ev["at_s"] >= seconds:
            raise ScheduleError(f"fault {i}: at_s {ev['at_s']} is outside the window of {seconds} s")
        who = previous
        if ev["replica"] == "seeded":  # a replica that is up
            who = rng.choice([k for k in range(n) if f"server-{k}" not in dead])
        previous = who
        sid = f"server-{who}"
        if getattr(mod, "RESTARTS", False):
            dead.discard(sid)
        if getattr(mod, "KILLS", False):
            if sid in dead:
                raise ScheduleError(f"fault {i}: {sid} is already down")
            if len(hosted[process_of[sid]]) > 1:
                raise ScheduleError(
                    f"fault {i}: {sid} shares its process with {len(hosted[process_of[sid]]) - 1} "
                    "other replicas, and a kill takes the whole process")
            dead.add(sid)
            # a replica set is rf of the n: at most f of ANY set are down
            # where at most f of the cluster are
            if len(dead) > f:
                raise ScheduleError(f"fault {i}: {len(dead)} replicas down at once, the "
                                    f"configuration tolerates f={f}")
            if len(dead | set(members)) > f:
                raise ScheduleError(
                    f"fault {i}: {sid} down beside the stated Byzantine "
                    f"{sorted(set(members) - dead)}: {len(dead | set(members))} faulty replicas "
                    f"at once, the configuration tolerates f={f} and no quorum is left to the "
                    "sets that hold them")
        events.append(dict(ev, index=i, server_id=sid, verb=mod))
    return events


async def run(pc, events: list, t_start: float, observe) -> list:
    """Execute the bound schedule against the window that opens at
    ``t_start`` (monotonic).  ``observe(server_id)`` is the harness's look at
    the service and at that replica, taken just before and just after each
    event; the readers get both.  Returns one record an event."""
    state: dict = {}
    records = []
    for ev in events:
        await asyncio.sleep(max(0.0, t_start + ev["at_s"] - time.monotonic()))
        before = observe(ev["server_id"])
        t0 = time.monotonic()
        timed = await ev["verb"].run(pc, ev, state)
        t1 = time.monotonic()
        records.append({
            "do": ev["do"], "server_id": ev["server_id"], "at_s": ev["at_s"],
            "started_s": t0 - t_start, "t_mono": t0, "seconds": t1 - t0, "timed": timed,
            "before": before, "after": observe(ev["server_id"]),
        })
    return records


def end_to_end(events: list, records: list) -> dict:
    """The end-to-end metrics the verbs bring: the summed seconds of the
    events of each verb that names one."""
    out: dict = {}
    for ev, rec in zip(events, records):
        name = getattr(ev["verb"], "END_TO_END", None)
        if name:
            out[name] = out.get(name, 0.0) + rec["seconds"]
    return out


def restarted(records) -> list:
    """The records of the events that brought a replica back: it did not
    answer before the event and answers after it."""
    return [r for r in records or ()
            if r["before"]["replica"] is None and r["after"]["replica"] is not None]


def service_gain(back: list, key: str):
    """What a counter of the service gained over the events of ``back``,
    from the look before each to the look after it."""
    return sum(r["after"]["service"][key] - r["before"]["service"][key] for r in back)
