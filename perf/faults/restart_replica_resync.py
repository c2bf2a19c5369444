"""The runbook's restart of a crashed replica (docs/OPERATIONS.md section 3,
first row): the killed replica's process is started again on its OWN storage
directory with ``--resync-on-boot``
(``ProcessCluster.restart_replica(server_id, resync=True)``), so READY means
"verified replay of its WAL and snapshot, then one digest-and-delta pass
against its peers".  The directory is left as the kill left it.  The command
to READY is in ``snap["end_to_end"]["recover_s"]`` and in the record's
``seconds``; the per-layer reader ``resync.ready_s`` reads it.

READY is then held to the configuration's fourth guarantee as far as the
replica's own record shows it (``not_caught_up``, below): ``/status``
``storage.resync`` has to tell of ONE full pass of THIS boot that ran to its
end against every other replica with no pull abandoned.  A boot that printed
READY without one (no ``--resync-on-boot``, a pass that did nothing, a pass
that ended INCOMPLETE) raises, and so does a boot that is not READY within
``READY_LIMIT_S``: the schedule then did not run to its end, the harness
closes the cluster and the run ends without a result (exit 3) in minutes, not
after the cluster's own READY time-out.  ``perf/tests/control_plain.py`` is
the run that has to end so.

A product whose ``restart_replica`` cannot pass the flag is refused when the
schedule is checked, before anything boots."""

import asyncio
import inspect
import os
import time

import schedule
from mochi_tpu.testing.process_cluster import ProcessCluster

RESTARTS = True
END_TO_END = "recover_s"
READY_LIMIT_S = 120.0

if "resync" not in inspect.signature(ProcessCluster.restart_replica).parameters:
    raise schedule.ScheduleError(
        "restart_replica_resync: this product's ProcessCluster.restart_replica takes no "
        "'resync', so a replica cannot be started again with --resync-on-boot")


class NotCaughtUp(Exception):
    """READY came without what the fourth guarantee states."""


def not_caught_up(report, others: int, asked_us: int, ready_us: int):
    """Why ``report`` (``storage.resync`` of the replica that has just printed
    READY) does not show a complete pass of this boot, or None where it does.
    ``others``: the replicas besides it; the command was given at ``asked_us``
    and READY read at ``ready_us`` (epoch microseconds).  A report that lacks
    ``began_epoch_us`` or the digest counters (an older product's) is held to
    the rest."""
    if not report:
        return "no resync pass on record: READY followed the replay alone"
    if not (report["full"] and report["complete"]):
        return f"the pass was not a full one run to its end: full={report['full']} complete={report['complete']}"
    abandoned = {sid: p["abandoned"] for sid, p in report["by_peer"].items() if p["abandoned"]}
    if abandoned or report["peers"] != others:
        return f"{report['peers']} of {others} peers asked, pulls abandoned: {abandoned}"
    if report.get("shards_compared", 1) <= 0:
        return "no peer's shard digests were compared: the pass decided nothing"
    if not asked_us <= report.get("began_epoch_us", asked_us) <= ready_us:
        return (f"the pass began at {report['began_epoch_us']}, outside this boot "
                f"({asked_us}..{ready_us}): the record is not of this READY")
    return None


def hold(pc, server_id: str, asked_us: int) -> None:
    """Raise unless the replica that has just printed READY is caught up, by its own ``/status``."""
    status = pc.replica_status(server_id, 10.0)
    why = not_caught_up(status and status["storage"].get("resync"), pc.n_servers - 1,
                        asked_us, time.time_ns() // 1000)
    if why:
        raise NotCaughtUp(f"{server_id} printed READY, but {why}")


async def run(pc, event, state):
    directory = os.path.join(pc.storage_root, event["server_id"])
    assert os.path.isdir(directory) and os.listdir(directory), directory
    asked_us, t0 = time.time_ns() // 1000, time.monotonic()
    await asyncio.wait_for(pc.restart_replica(event["server_id"], resync=True), READY_LIMIT_S)
    ready_s = time.monotonic() - t0
    hold(pc, event["server_id"], asked_us)
    return {"ready_s": ready_s}
