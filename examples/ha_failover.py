"""Broker HA end to end: SIGKILL the primary mid-stream, keep producing.

The crash the replication layer exists for (``docs/replication.md``), run
as a demo: a producer streams numbered records through a ``FailoverBroker``
while the durable primary — a separate OS process — is SIGKILLed halfway.
The standby ``ReplicaFollower`` (which has been pulling the primary's CRC
frames all along) is promoted at a fenced epoch, the client re-sends its
unconfirmed tail, and the stream resumes. At the end the record set read
back from the promoted broker must cover *every* produced record — the
at-least-once contract: nothing committed is lost, duplicates collapse
under idempotent-by-key consumption (here, a ``set``). The killed primary
is then restarted on its old log to show the zombie getting fenced.

Run:  PYTHONPATH=src python examples/ha_failover.py --batches 60
"""
import argparse
import multiprocessing as mp
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def primary_main(root: str, sock: str) -> None:
    """Primary process: durable broker served on a Unix socket."""
    import threading

    from repro.core import Broker
    from repro.core.broker import COMMIT_TOPIC
    from repro.data import DurableLogFactory, serve_broker

    factory = DurableLogFactory(root)
    broker = Broker(log_factory=factory, commit_topic=COMMIT_TOPIC)
    factory.restore(broker)                # a restarted zombie reopens its log
    broker.restore_commits()
    serve_broker(broker, sock)
    print(f"[primary pid={os.getpid()}] serving {sock}", flush=True)
    threading.Event().wait()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=60)
    ap.add_argument("--batch", type=int, default=50, help="records per batch")
    args = ap.parse_args()

    from repro.core.broker import BrokerFencedError, OffsetRange
    from repro.data import FailoverBroker, RemoteBroker, ReplicaFollower
    from repro.utils import cpu_only_children

    work = tempfile.mkdtemp(prefix="ha-failover-")
    psock = os.path.join(work, "p.sock")
    proc = mp.get_context("spawn").Process(
        target=primary_main, args=(os.path.join(work, "primary"), psock),
        name="primary-broker")
    with cpu_only_children():      # a broker process is data plane
        proc.start()
    while not os.path.exists(psock):
        time.sleep(0.01)

    follower = ReplicaFollower(psock, os.path.join(work, "replica"),
                               poll_interval=0.005)
    standby = follower.serve(os.path.join(work, "f.sock"))
    follower.start()

    client = FailoverBroker([psock, standby])
    client.create_topic("t", 2)
    kill_at = args.batches // 2
    t0 = time.perf_counter()
    for n in range(args.batches):
        if n == kill_at:
            proc.kill()                    # SIGKILL, mid-stream, no goodbye
            print(f"[client] SIGKILLed the primary before batch {n}")
        client.produce_many(
            "t", [(None, n * args.batch + i) for i in range(args.batch)],
            partition=n % 2)
    wall = time.perf_counter() - t0
    assert client.flush(timeout=30.0), "replica never caught up"
    proc.join(timeout=10)

    # every produced record must be readable from the promoted broker;
    # resend duplicates collapse in the set (the idempotent-sink stand-in)
    seen: set[int] = set()
    for p in range(2):
        end = client.end_offset("t", p)
        for rec in client.read(OffsetRange("t", p, 0, end)):
            seen.add(rec.value)
    produced = args.batches * args.batch
    missing = set(range(produced)) - seen
    assert not missing, f"lost committed records: {sorted(missing)[:10]}"
    dup = (sum(client.end_offsets("t")) - produced)
    print(f"[client] {args.batches} batches x {args.batch} records in "
          f"{wall:.2f}s across the kill; {client.failovers} failover to "
          f"epoch {client.epoch}; all {produced} records survived "
          f"({dup} duplicate{'s' if dup != 1 else ''} from the resend "
          f"window, absorbed by the set)")

    # restart the dead primary on its old log: it comes back writable at
    # epoch 0, i.e. a zombie — fence it and show a direct write bouncing
    os.unlink(psock)                       # SIGKILL left the socket file
    zombie = mp.get_context("spawn").Process(
        target=primary_main, args=(os.path.join(work, "primary"), psock),
        name="zombie-primary")
    with cpu_only_children():
        zombie.start()
    while not os.path.exists(psock):
        time.sleep(0.01)
    time.sleep(0.1)
    fenced = client.fence_stale()
    direct = RemoteBroker(psock)
    try:
        direct.produce("t", -1, partition=0)
        raise SystemExit("zombie accepted a write — fencing is broken")
    except BrokerFencedError as e:
        print(f"[client] zombie primary fenced ({len(fenced)} broker): {e}")
    finally:
        direct.close()
    client.produce("t", produced, partition=0)   # real primary still writable

    client.close()
    follower.stop()
    zombie.kill()
    zombie.join(timeout=10)
    shutil.rmtree(work, ignore_errors=True)
    print("ha failover complete: primary SIGKILLed, follower promoted, "
          "stream resumed, zombie fenced — no committed record lost")


if __name__ == "__main__":
    main()
