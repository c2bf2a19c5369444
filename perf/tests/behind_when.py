"""WHEN each record that a restarted replica is behind on was committed: one
run of a cell with a fault schedule, as ``perf/run.py`` makes it, with the
direct read-back's ``behind`` count (``reference.check_direct``: the records
the replica, asked alone after the window, holds older than their newest
acknowledged update) taken apart record by record.

    python perf/tests/behind_when.py --workload <cell> --seed <n> --seconds <s> [--plain]

For each such record: the oldest acknowledged update the replica lacks, its
issue and acknowledgement on the window's clock, and the phase its
acknowledgement fell in: ``before_kill`` (inside the read-back's slack),
``down`` (until the restart's command), ``booting`` (command to READY; with
``--resync-on-boot``, until the pass began), ``during_pass`` (from
``storage.resync.began_epoch_us`` to READY), ``after_ready``.  A replica that
made a complete pass may be behind on nothing acknowledged before the pass
began (the configuration's fourth guarantee); ``during_pass`` and
``after_ready`` are Write2s that a serving replica did not get.  One line
``[behind] {...}`` on standard output before the result's, and the run's own
exit code.  ``--plain`` runs the schedule with the shipped plain restart
(``perf/faults/restart_replica.py``) in the place of every other restart verb:
the same instants without ``--resync-on-boot``, the price the pass is paid for.
Takes the same arguments as ``perf/run.py`` (``--rehearse`` for the CPU
rehearsal).
"""

import json
import math
import sys
import time

import control  # noqa: F401  (puts perf/ on the path)
import reference as ref
import schedule

PHASES = ("before_kill", "down", "booting", "during_pass", "after_ready")


def phases_of(records: list, mono_of_epoch) -> dict:
    """{server id: [(phase, the monotonic time it ends at)]} from a schedule's records."""
    out = {}
    for kill in records:
        if kill["after"]["replica"] is not None:
            continue
        back = next(r for r in schedule.restarted(records) if r["server_id"] == kill["server_id"])
        ready = back["t_mono"] + back["timed"]["ready_s"]
        report = back["after"]["replica"]["storage"].get("resync") or {}
        began = mono_of_epoch(report["began_epoch_us"]) if "began_epoch_us" in report else ready
        out[kill["server_id"]] = [("before_kill", kill["t_mono"]), ("down", back["t_mono"]),
                                  ("booting", began), ("during_pass", ready), ("after_ready", math.inf)]
    return out


def take_apart(direct: dict, hist: dict, records: list, t_start: float, mono_of_epoch) -> dict:
    """``direct`` and ``hist`` as ``reference.check_direct`` takes them."""
    counts, rows = dict.fromkeys(PHASES, 0), []
    for sid, bounds in phases_of(records, mono_of_epoch).items():
        for rec, got in direct.get(sid, {}).items():
            w = got and hist[rec].writes.get(got[:2])
            if not w or w[1] >= hist[rec].newest_issue_acked_before(math.inf):
                continue
            # the oldest acknowledged update issued after the one it holds was acknowledged
            issued, acked = min((x[0], x[1]) for x in hist[rec].writes.values()
                                if x[0] > w[1] and x[1] < math.inf)
            phase = next(name for name, until in bounds if acked < until)
            counts[phase] += 1
            rows.append({"server_id": sid, "record": rec, "phase": phase,
                         "lacks_issued_s": issued - t_start, "lacks_acked_s": acked - t_start,
                         "holds_acked_s": w[1] - t_start if w[1] > -math.inf else None})
        counts["bounds_s"] = {name: until - t_start for name, until in bounds[:-1]}
    return {"behind": len(rows), **counts, "records": sorted(rows, key=lambda r: r["lacks_acked_s"])}


def main(argv) -> int:
    plain = "--plain" in argv
    argv = [a for a in argv if a != "--plain"]
    if plain:
        load = schedule.load_verb

        def load_plain(faults_dir, verb):
            mod = load(faults_dir, verb)
            return load(faults_dir, "restart_replica") if getattr(mod, "RESTARTS", False) else mod

        schedule.load_verb = load_plain
    offset = time.time() - time.monotonic()  # one host: both clocks tick together
    check, run_schedule, window = ref.check_direct, schedule.run, {}

    async def timed_schedule(pc, events, t_start, observe):
        window["t_start"] = t_start
        return await run_schedule(pc, events, t_start, observe)

    def check_direct(direct, hist, records, slack_s, quorum):
        apart = take_apart(direct, hist, records, window["t_start"], lambda us: us / 1e6 - offset)
        print("[behind]", json.dumps(dict(apart, plain=plain)), flush=True)
        return check(direct, hist, records, slack_s, quorum)

    ref.check_direct, schedule.run = check_direct, timed_schedule
    return control.run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
