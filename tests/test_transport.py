"""Socket transport (doc/serving.md "Cross-host fleet"): length-prefixed
framing with torn-tail tolerance, the per-connection reconnect/backoff
state machine, deadline propagation over the wire, the server's
dedupe/hello/deadline-shed admission, hedged retries through the fleet
router, the transport-qualified compare join — and the cross-host chaos
e2e: a real `paddle serve --listen` pair behind `paddle serve-fleet
--replica_addr`, surviving net.drop resets and a replica kill with
every request answered exactly once, plus pipe-vs-socket golden parity
and the `paddle trace` net.* hop reconstruction."""

import importlib.util
import json
import os
import socket
import struct
import subprocess
import sys

import pytest

from paddle_tpu.observability import metrics as obs
from paddle_tpu.observability.analyze import load_run
from paddle_tpu.observability.compare import _serve_key
from paddle_tpu.resilience import faultinject
from paddle_tpu.serving import transport
from paddle_tpu.serving.fleet import FleetRouter, merge_windows
from paddle_tpu.serving.transport import (
    EngineSocketServer,
    FrameError,
    FrameReader,
    SocketEngineClient,
    SocketReplica,
    SocketTransport,
    encode_frame,
    parse_addr,
)
from paddle_tpu.utils import concurrency as cc
from paddle_tpu.utils.flags import flag_values
from paddle_tpu.utils.retry import RetryPolicy

pytestmark = pytest.mark.net

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the race spec's FakeWire/_pipe/HedgeReplica are the reference
# in-process wire + replica fakes — reuse them rather than fork copies
# that could drift (the test_serve_fleet idiom)
_spec = importlib.util.spec_from_file_location(
    "spec_transport",
    os.path.join(REPO, "tests", "race_specs", "spec_transport.py"))
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)
FakeWire = _mod.FakeWire
_pipe = _mod._pipe
HedgeReplica = _mod.HedgeReplica


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    obs.registry().reset()
    yield
    obs.configure("")
    faultinject.configure("")


def _wait_for(cond, timeout=30.0, msg="condition"):
    deadline = cc.monotonic() + timeout
    while cc.monotonic() < deadline:
        if cond():
            return
        cc.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


# -------------------------------------------------------------- framing


def test_frame_roundtrip_and_torn_tail():
    doc = {"id": "r1", "prompt": [1, 2, 3], "nested": {"a": 1}}
    data = encode_frame(doc)
    reader = FrameReader()
    # a torn tail: the frame arrives in three fragments, the doc decodes
    # only once the final byte lands — and exactly once
    assert reader.feed(data[:3]) == []
    assert reader.feed(data[3:-2]) == []
    assert reader.feed(data[-2:]) == [doc]
    assert reader.pending_bytes() == 0
    # two frames in one read plus a torn third
    d2, d3 = {"id": "a"}, {"id": "b"}
    blob = encode_frame(d2) + encode_frame(d3) + encode_frame(doc)[:5]
    assert reader.feed(blob) == [d2, d3]
    assert reader.pending_bytes() == 5


def test_frame_reader_skips_garbage_keeps_stream():
    reader = FrameReader()
    garbage = b"\x00\x00\x00\x04not{"  # valid length, invalid JSON
    good = encode_frame({"id": "ok"})
    out = reader.feed(garbage[:8] + good)
    # the undecodable frame is skipped, the stream stays aligned
    assert out == [{"id": "ok"}]


def test_frame_oversized_header_rejected():
    reader = FrameReader()
    huge = struct.pack("!I", transport.MAX_FRAME_BYTES + 1)
    with pytest.raises(FrameError):
        reader.feed(huge + b"x")
    with pytest.raises(FrameError):
        encode_frame({"id": "x" * (transport.MAX_FRAME_BYTES + 16)})


def test_parse_addr():
    assert parse_addr("10.0.0.2:9000") == ("10.0.0.2", 9000)
    assert parse_addr(":0") == ("0.0.0.0", 0)
    with pytest.raises(ValueError):
        parse_addr("no-port")


# ------------------------------------------------ transport state machine


def test_transport_reconnects_after_drop_and_redelivers():
    decoded, conns = [], []
    lock = cc.Lock()

    def serve(wire):
        reader = FrameReader()
        while True:
            data = wire.recv(65536)
            if not data:
                return
            for doc in reader.feed(data):
                with lock:
                    decoded.append(doc)

    def connect(addr):
        a, b = _pipe()
        with lock:
            conns.append(b)
        cc.Thread(target=serve, args=(b,), daemon=True).start()
        return a

    policy = RetryPolicy(max_attempts=100, base_delay=0.001,
                         max_delay=0.005, jitter=0.0, name="net.connect")
    t = SocketTransport("c0", "fake:0", on_frame=lambda d: None,
                        policy=policy, connect_fn=connect)
    t.start()
    _wait_for(lambda: t.state == transport.UP, msg="first connect")
    assert t.send({"id": "before"})
    with lock:
        conns[0].close()  # the drop
    _wait_for(lambda: t.reconnects >= 1, msg="reconnect")
    _wait_for(lambda: t.send({"id": "after"}), msg="send on new wire")
    _wait_for(lambda: any(d.get("id") == "after" for d in decoded),
              msg="delivery on reconnected wire")
    t.close()
    assert t.join(timeout=10.0)
    assert t.state == transport.CLOSED
    ids = [d["id"] for d in decoded]
    assert len(ids) == len(set(ids)), ids  # nothing decodes twice


def test_transport_backoff_budget_exhaustion_closes():
    attempts = []

    def connect(addr):
        attempts.append(cc.monotonic())
        raise OSError("connection refused")

    policy = RetryPolicy(max_attempts=3, base_delay=0.01, max_delay=0.02,
                         multiplier=2.0, jitter=0.0, name="net.connect")
    t = SocketTransport("c0", "fake:0", on_frame=lambda d: None,
                        policy=policy, connect_fn=connect)
    t.start()
    _wait_for(t.closed, msg="budget exhaustion")
    assert t.join(timeout=10.0)
    assert t.state == transport.CLOSED
    assert len(attempts) == 3  # the budget, exactly
    # CLOSED is terminal: sends refuse instead of buffering silently
    assert t.send({"id": "x"}) is False


# --------------------------------------------- replica + server contract


class _ManualFut:
    def __init__(self):
        self._ev = cc.Event()
        self._res = None

    def done(self):
        return self._ev.is_set()

    def result(self, timeout=None):
        if not self._ev.wait(timeout):
            raise TimeoutError("unresolved")
        return self._res

    def resolve(self, res):
        self._res = res
        self._ev.set()


class _Res:
    def __init__(self, tokens=(1, 2), outcome="ok"):
        self.outcome = outcome
        self.tokens = list(tokens)
        self.error = ""
        self.retry_after_s = None


class _FakeEngine:
    """Engine duck-type with manually-resolved futures so tests control
    exactly when answers cross the wire."""

    def __init__(self):
        self.lock = cc.Lock()
        self.subs = {}  # rid -> (fut, timeout_s)

    def submit(self, prompt, max_new_tokens=None, rid=None, timeout_s=None,
               replay=False, trace=""):
        fut = _ManualFut()
        with self.lock:
            self.subs[rid] = (fut, timeout_s)
        return fut

    def status(self):
        return {"state": "serving", "queue_depth": 0, "occupancy": 0.0}


def test_replica_stamps_deadline_once_and_delivers():
    eng, eng2 = _FakeEngine(), _FakeEngine()
    srv = EngineSocketServer(eng, "127.0.0.1:0")
    srv2 = EngineSocketServer(eng2, "127.0.0.1:0")
    srv.start(), srv2.start()
    try:
        got = []

        def deliver(name, doc):
            got.append((name, doc))

        rep = SocketReplica("replica-0", srv.address, deliver=deliver,
                            timeout_s=30.0).start()
        doc = {"id": "d0", "prompt": [1, 2], "max_new_tokens": 2}
        _wait_for(lambda: rep.send(doc), msg="send over loopback")
        # the wall-clock deadline landed in the SHARED doc, once
        assert "deadline_unix" in doc
        stamped = doc["deadline_unix"]
        assert stamped == pytest.approx(transport.wall_time() + 30.0, abs=5.0)
        _wait_for(lambda: "d0" in eng.subs, msg="server submit")
        fut, timeout_s = eng.subs["d0"]
        # the server shrank the budget to the wire remainder
        assert timeout_s is not None and 0 < timeout_s <= 30.0
        # a re-offer to ANOTHER replica keeps the ORIGINAL deadline even
        # though replica-1's own timeout budget is far larger
        rep2 = SocketReplica("replica-1", srv2.address, deliver=deliver,
                             timeout_s=600.0).start()
        _wait_for(lambda: rep2.send(doc), msg="re-offer send")
        assert doc["deadline_unix"] == stamped
        fut.resolve(_Res(tokens=[7, 8]))
        _wait_for(lambda: len(got) >= 1, msg="answer delivery")
        name, ans = got[0]
        assert name == "replica-0" and ans["id"] == "d0"
        assert ans["outcome"] == "ok" and ans["tokens"] == [7, 8]
        rep.kill(), rep2.kill()
        assert rep.join(10.0) and rep2.join(10.0)
    finally:
        srv.close(), srv2.close()


def test_server_sheds_expired_deadline_on_arrival():
    eng = _FakeEngine()
    srv = EngineSocketServer(eng, "127.0.0.1:0")
    srv.start()
    try:
        got = []
        rep = SocketReplica("replica-0", srv.address,
                            deliver=lambda n, d: got.append(d),
                            timeout_s=30.0).start()
        doc = {"id": "late", "prompt": [1],
               "deadline_unix": transport.wall_time() - 5.0}
        _wait_for(lambda: rep.send(doc), msg="send expired doc")
        _wait_for(lambda: len(got) >= 1, msg="shed answer")
        assert got[0]["id"] == "late"
        assert got[0]["outcome"] == "timeout", got[0]
        # the engine never saw it — the remote replica shed locally
        assert "late" not in eng.subs
        rep.kill()
        assert rep.join(10.0)
    finally:
        srv.close()


def test_reconnect_hello_answer_arrives_exactly_once():
    """Kill the live connection while a request is in flight: the
    replica reconnects, the hello names it outstanding, the server
    (which still holds it in flight) answers on the NEW wire — exactly
    once, no re-submit."""
    eng = _FakeEngine()
    srv = EngineSocketServer(eng, "127.0.0.1:0")
    hellos = []
    handle = srv._handle

    def handle_and_note_hello(doc, wire):
        handle(doc, wire)
        if doc.get("op") == "hello":
            hellos.append(doc)

    srv._handle = handle_and_note_hello
    srv.start()
    try:
        got = []
        rep = SocketReplica("replica-0", srv.address,
                            deliver=lambda n, d: got.append(d),
                            timeout_s=60.0).start()
        _wait_for(lambda: rep.send({"id": "h0", "prompt": [1],
                                    "max_new_tokens": 1}), msg="send")
        _wait_for(lambda: "h0" in eng.subs, msg="server submit")
        with rep._lock:
            t = rep._transport
        # sever the wire server-side: the client must reconnect
        with srv._lock:
            conn = srv._conn
        n_hellos = len(hellos)
        transport._close_wire(conn)
        _wait_for(lambda: t.reconnects >= 1, msg="reconnect")
        # the request must still be in flight when the server reads the
        # reconnect's hello: answered first, the hello would (rightly,
        # the wire is at-least-once) have it sent a second time
        _wait_for(lambda: len(hellos) > n_hellos, msg="hello after reconnect")
        eng.subs["h0"][0].resolve(_Res())
        _wait_for(lambda: len(got) >= 1, msg="answer after reconnect")
        cc.sleep(0.2)  # absorb any (wrong) duplicate delivery
        assert [d["id"] for d in got] == ["h0"]
        # in flight during the hello meant: no duplicate engine submit
        assert len(eng.subs) == 1
        rep.kill()
        assert rep.join(10.0)
    finally:
        srv.close()


def test_server_dedupes_by_id_and_resends_answered():
    eng = _FakeEngine()
    srv = EngineSocketServer(eng, "127.0.0.1:0")
    srv.start()
    try:
        cli = SocketEngineClient(srv.address)
        cli.start()
        fut = cli.submit({"id": "q0", "prompt": [1], "max_new_tokens": 1})
        _wait_for(lambda: "q0" in eng.subs, msg="submit")
        eng.subs["q0"][0].resolve(_Res(tokens=[3]))
        assert fut.result(timeout=30)["tokens"] == [3]
        # duplicate delivery (a hedge loser, a net.dup): the stored
        # answer is re-sent, the engine is NOT re-submitted
        fut2 = cli.submit({"id": "q0", "prompt": [1], "max_new_tokens": 1})
        assert fut2.result(timeout=30)["tokens"] == [3]
        assert len(eng.subs) == 1
        cli.close()
    finally:
        srv.close()


def test_replica_health_stale_without_pongs():
    # a listener that accepts nothing: the TCP connect succeeds (backlog)
    # but no pong ever comes back — health must say stale, not lie
    lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lst.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    lst.bind(("127.0.0.1", 0))
    lst.listen(1)
    try:
        addr = f"127.0.0.1:{lst.getsockname()[1]}"
        rep = SocketReplica("replica-0", addr,
                            deliver=lambda n, d: None).start()
        h = rep.health(cc.monotonic())
        assert h.get("stale") is True
        assert "no pong" in h.get("detail", "")
        rep.kill()
        assert rep.join(10.0)
    finally:
        lst.close()


# ----------------------------------------------------- hedging (router)


def test_router_hedges_slow_replica_first_answer_wins():
    emitted = []
    reps = [HedgeReplica("replica-0", delay_s=0.5),
            HedgeReplica("replica-1", delay_s=0.01)]
    router = FleetRouter(reps, emit=emitted.append, poll_s=0.005,
                         health_period_s=0.0, restart_base_delay=0.01,
                         hedge_after=0.03)
    for r in reps:
        r.deliver = router.deliver
    router.start()
    ids = [f"g{i}" for i in range(4)]
    for rid in ids:
        assert router.submit({"id": rid, "prompt": [2],
                              "max_new_tokens": 1})
    box = {}
    t = cc.Thread(target=lambda: box.setdefault("rc", router.run()),
                  daemon=True)
    t.start()
    router.note_eof()
    t.join(timeout=60.0)
    assert not t.is_alive()
    assert box["rc"] == 0
    router.shutdown(timeout=10.0)
    assert [d["id"] for d in emitted] == ids  # exactly once, in order
    st = router.status()
    # requests stuck on the slow owner were hedged to the fast replica,
    # and the fast answer won at least once
    assert st["hedges"] >= 1, st
    assert st["hedge_wins"] >= 1, st
    assert st["hedge_wins"] <= st["hedges"], st
    # the loser's late answer was absorbed, never emitted
    assert st["duplicate_answers"] <= st["hedges"], st


def test_hedge_disabled_by_default():
    emitted = []
    reps = [HedgeReplica("replica-0", delay_s=0.2),
            HedgeReplica("replica-1", delay_s=0.01)]
    router = FleetRouter(reps, emit=emitted.append, poll_s=0.005,
                         health_period_s=0.0, restart_base_delay=0.01)
    for r in reps:
        r.deliver = router.deliver
    router.start()
    assert router.submit({"id": "n0", "prompt": [2], "max_new_tokens": 1})
    box = {}
    t = cc.Thread(target=lambda: box.setdefault("rc", router.run()),
                  daemon=True)
    t.start()
    router.note_eof()
    t.join(timeout=60.0)
    assert not t.is_alive() and box["rc"] == 0
    router.shutdown(timeout=10.0)
    assert router.status()["hedges"] == 0


# -------------------------------------------- compare join + flag helper


def test_merge_windows_stamps_transport():
    w = {"engine": "continuous", "completed": 1, "gen_tokens": 2,
         "arrived": 1}
    rec = merge_windows([w], rate_rps=1.0, rung=0, window_s=1.0,
                        router_s=0.1, transport="tcp")
    assert rec["transport"] == "tcp"
    rec2 = merge_windows([w], rate_rps=1.0, rung=0, window_s=1.0)
    assert "transport" not in rec2


def test_serve_key_transport_qualifies_on_collision():
    seen = set()
    base = _serve_key(4.0, 0, seen, engine="continuous", pipeline="on",
                      replicas=2, transport="pipe")
    eng = _serve_key(4.0, 1, seen, engine="continuous", pipeline="on",
                     replicas=2, transport="pipe")
    pipe_q = _serve_key(4.0, 2, seen, engine="continuous", pipeline="on",
                        replicas=2, transport="pipe")
    tcp = _serve_key(4.0, 3, seen, engine="continuous", pipeline="on",
                     replicas=2, transport="tcp")
    assert base == "serve.x2.4rps."
    assert eng == "serve.continuous.x2.4rps."
    assert pipe_q == "serve.continuous.pipe-on.x2.4rps."
    # the 4th same-(engine, pipeline, rate) rung: transport breaks the tie
    assert tcp == "serve.continuous.pipe-on.net-tcp.x2.4rps."
    # a one-transport-per-artifact A/B joins UNQUALIFIED on offered load
    assert _serve_key(4.0, 0, set(), engine="continuous", pipeline="on",
                      replicas=2, transport="tcp") == base


def test_flag_values_collects_repeats_and_commas():
    argv = ["--replica_addr=a:1", "--x=1", "--replica_addr=b:2,c:3",
            "--replica_addr=d:4"]
    assert flag_values(argv, "replica_addr") == ["a:1", "b:2", "c:3", "d:4"]
    assert flag_values(argv, "missing") == []


# ------------------------------------------------------------ chaos e2e


SERVE_CONFIG = """
import sys
sys.path.insert(0, {demo!r})
from paddle.trainer_config_helpers import *
from seqToseq_net import gru_encoder_decoder

settings(batch_size=2, learning_rate=1e-3, learning_method=AdamOptimizer())
gru_encoder_decoder(source_dict_dim=50, target_dict_dim=50,
                    is_generating=True, word_vector_dim=16,
                    encoder_size=16, decoder_size=16, beam_size=1,
                    max_length=6)
"""

SUBPROC_ENV = dict(
    os.environ, JAX_PLATFORMS="cpu",
    PYTHONPATH=f"{REPO}:{os.path.join(REPO, 'compat')}",
)


def _write_config(tmp_path):
    cfg = tmp_path / "serve_conf.py"
    cfg.write_text(SERVE_CONFIG.format(
        demo=os.path.join(REPO, "demo", "seqToseq")))
    return cfg


def _drain(pipe, sink):
    def run():
        for line in pipe:
            sink.append(line)
    t = cc.Thread(target=run, daemon=True)
    t.start()
    return t


def _start_listen_server(tmp_path, cfg, idx, metrics_path=None, env=None):
    """One `paddle serve --listen 127.0.0.1:0` subprocess; returns
    (proc, addr, stderr_sink) once the bound-address banner prints."""
    argv = [sys.executable, "-m", "paddle_tpu.cli", "serve",
            f"--config={cfg}", "--use_tpu=0", "--listen=127.0.0.1:0",
            "--serve_slots=2", "--serve_prompt_tokens=4",
            "--serve_decode_block=1",
            f"--compile_cache_dir={tmp_path / 'ccache'}"]
    if metrics_path:
        argv.append(f"--metrics_path={metrics_path}")
    proc = subprocess.Popen(argv, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            env=env or SUBPROC_ENV, cwd=str(tmp_path))
    errs = []
    addr = None
    deadline = cc.monotonic() + 300.0
    marker = "# paddle serve: listening on "
    while cc.monotonic() < deadline:
        line = proc.stderr.readline()
        if not line:
            break
        errs.append(line)
        if line.startswith(marker):
            addr = line[len(marker):].strip()
            break
    assert addr, f"server {idx} never printed its address: {''.join(errs)}"
    # keep both pipes drained so the child never blocks on a full pipe
    _drain(proc.stderr, errs)
    _drain(proc.stdout, errs)
    return proc, addr, errs


def _fleet_requests(n):
    """The seeded schedule_requests workload both transports replay."""
    import numpy as np

    from paddle_tpu.observability import serving

    prng_holder = {}

    def prompt_fn(rng, i):
        return rng.randint(2, 49, size=int(rng.randint(1, 5))).tolist()

    reqs = serving.schedule_requests(50.0, n, 7, rung=0,
                                     prompt_fn=prompt_fn)
    del np, prng_holder
    return [{"id": r.rid, "prompt": list(r.prompt or [2, 3]),
             "max_new_tokens": int(getattr(r, "max_new", None) or 2)}
            for r in reqs]


def _answers(stdout_text):
    out = []
    for line in stdout_text.splitlines():
        line = line.strip()
        if line.startswith("{"):
            doc = json.loads(line)
            if "outcome" in doc:
                out.append(doc)
    return out


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_socket_fleet_drop_and_replica_death(tmp_path):
    """THE acceptance scenario: two `paddle serve --listen` replicas
    behind `paddle serve-fleet --replica_addr`; the router takes an
    injected net.drop (connection reset mid-stream) AND one server
    process is killed mid-load. The transport reconnects with backoff,
    the hello handshake re-offers undelivered work, the death path
    re-offers the killed replica's outstanding to the survivor — and
    every request id is answered EXACTLY once, in order, rc 0."""
    cfg = _write_config(tmp_path)
    run_dir = tmp_path / "run"
    docs = _fleet_requests(8)
    ids = [d["id"] for d in docs]
    p0, addr0, errs0 = _start_listen_server(tmp_path, cfg, 0)
    p1, addr1, errs1 = _start_listen_server(tmp_path, cfg, 1)
    try:
        router = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.cli", "serve-fleet",
             f"--replica_addr={addr0}", f"--replica_addr={addr1}",
             "--restart_base_delay=0.01", "--restart_budget=1",
             "--io_retry_attempts=2", "--io_retry_base_delay=0.05",
             "--fault_spec=net.drop=raise@3",
             f"--fleet_status_dir={tmp_path / 'fs'}",
             f"--metrics_path={run_dir}"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, env=SUBPROC_ENV,
            cwd=str(tmp_path))
        rerrs = []
        _drain(router.stderr, rerrs)
        for d in docs:
            router.stdin.write(json.dumps(d) + "\n")
        router.stdin.close()  # EOF batch: everything must be answered
        answers = []
        killed = False
        deadline = cc.monotonic() + 540.0
        while len(answers) < len(ids) and cc.monotonic() < deadline:
            line = router.stdout.readline()
            if not line:
                break
            line = line.strip()
            if line.startswith("{") and "outcome" in line:
                answers.append(json.loads(line))
            if len(answers) >= 2 and not killed:
                p1.kill()  # one replica dies mid-load
                killed = True
        rc = router.wait(timeout=60)
        assert killed, "load finished before the kill — raise n_requests"
        assert rc == 0, (rc, "".join(rerrs)[-4000:])
        got = [d["id"] for d in answers]
        assert got == ids, (got, "".join(rerrs)[-3000:])
        assert all(d["outcome"] == "ok" for d in answers), answers
        # the drills actually fired: the run_end counter snapshot shows
        # at least one re-established connection and the death
        recs = [r for rs in load_run(str(run_dir)).values() for r in rs]
        end = [r for r in recs if r.get("kind") == "run_end"]
        assert end, recs[-3:]
        counters = end[0].get("counters") or {}
        assert counters.get("net.reconnects", 0) >= 1, counters
        assert counters.get("fleet.deaths", 0) >= 1, counters
        assert counters.get("fleet.routed", 0) >= len(ids), counters
    finally:
        for p in (p0, p1):
            if p.poll() is None:
                p.kill()
            p.wait(timeout=30)


@pytest.mark.chaos
@pytest.mark.slow
def test_golden_parity_pipe_fleet_vs_socket_fleet(tmp_path):
    """The same seeded schedule_requests workload through a pipe fleet
    and a socket fleet must produce IDENTICAL answers per id — the
    transport moves bytes, it must never move numerics."""
    cfg = _write_config(tmp_path)
    docs = _fleet_requests(6)
    ids = [d["id"] for d in docs]
    reqs = "\n".join(json.dumps(d) for d in docs) + "\n"

    pipe_out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "serve-fleet",
         f"--config={cfg}", "--use_tpu=0", "--fleet_replicas=2",
         f"--fleet_status_dir={tmp_path / 'fs_pipe'}",
         "--serve_slots=2", "--serve_prompt_tokens=4",
         "--serve_decode_block=1", "--restart_base_delay=0.01",
         f"--compile_cache_dir={tmp_path / 'ccache'}"],
        input=reqs, capture_output=True, text=True, timeout=600,
        env=SUBPROC_ENV, cwd=str(tmp_path))
    assert pipe_out.returncode == 0, pipe_out.stderr[-4000:]
    pipe_answers = {d["id"]: d for d in _answers(pipe_out.stdout)}
    assert sorted(pipe_answers) == sorted(ids)

    p0, addr0, _ = _start_listen_server(tmp_path, cfg, 0)
    p1, addr1, _ = _start_listen_server(tmp_path, cfg, 1)
    try:
        sock_out = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.cli", "serve-fleet",
             f"--replica_addr={addr0}", f"--replica_addr={addr1}",
             f"--fleet_status_dir={tmp_path / 'fs_sock'}"],
            input=reqs, capture_output=True, text=True, timeout=600,
            env=SUBPROC_ENV, cwd=str(tmp_path))
        assert sock_out.returncode == 0, sock_out.stderr[-4000:]
        sock_answers = {d["id"]: d for d in _answers(sock_out.stdout)}
        assert sorted(sock_answers) == sorted(ids)
        for rid in ids:
            a, b = pipe_answers[rid], sock_answers[rid]
            assert a["outcome"] == b["outcome"] == "ok", (rid, a, b)
            assert a["tokens"] == b["tokens"], (rid, a, b)
    finally:
        for p in (p0, p1):
            if p.poll() is None:
                p.kill()
            p.wait(timeout=30)


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.trace
def test_socket_fleet_trace_net_hops_and_hedge_win(tmp_path):
    """`paddle trace` over a socket-fleet run: net.connect hops land in
    the router stream, answered requests carry net.rpc hops in their
    timelines, an injected net.stall (wedged read — pongs stop, answers
    stop) forces a hedge whose win shows up in the counters and whose
    hedge bucket is attributed in the tail table."""
    from paddle_tpu.observability.tracing import analyze_trace

    cfg = _write_config(tmp_path)
    run_dir = tmp_path / "run"
    docs = _fleet_requests(8)
    ids = [d["id"] for d in docs]
    reqs = "\n".join(json.dumps(d) for d in docs) + "\n"
    # replica streams INSIDE the run dir, where fleet_stream_dirs
    # discovers them next to the router's own stream
    p0, addr0, _ = _start_listen_server(
        tmp_path, cfg, 0,
        metrics_path=run_dir / "fleet_status" / "replica-0")
    p1, addr1, _ = _start_listen_server(
        tmp_path, cfg, 1,
        metrics_path=run_dir / "fleet_status" / "replica-1")
    try:
        out = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.cli", "serve-fleet",
             f"--replica_addr={addr0}", f"--replica_addr={addr1}",
             "--hedge_after=0.5",
             # wedge one replica connection's read loop mid-run: its
             # pongs and answers stop, outstanding work there hedges
             "--fault_spec=net.stall=sleep:8@5",
             f"--fleet_status_dir={tmp_path / 'fs'}",
             f"--metrics_path={run_dir}"],
            input=reqs, capture_output=True, text=True, timeout=600,
            env=SUBPROC_ENV, cwd=str(tmp_path))
        assert out.returncode == 0, out.stderr[-4000:]
        answers = _answers(out.stdout)
        got = [d["id"] for d in answers]
        assert got == ids, (got, out.stderr[-3000:])
        recs = [r for rs in load_run(str(run_dir)).values() for r in rs]
        end = [r for r in recs if r.get("kind") == "run_end"]
        assert end, recs[-3:]
        counters = end[0].get("counters") or {}
        assert counters.get("net.hedges", 0) >= 1, counters
        assert counters.get("net.hedge_wins", 0) >= 1, counters
        # the net.* hops are real span records in the router stream
        span_names = {r.get("name") for r in recs if r.get("kind") == "span"}
        assert "net.connect" in span_names, span_names
        assert "net.rpc" in span_names, span_names
        assert "net.hedge" in span_names, span_names

        doc = analyze_trace([str(run_dir)])
        # router stream plus both replica streams were discovered
        assert len(doc["streams"]) >= 3, doc["streams"]
        recon = {t["rid"]: t for t in doc["requests"].values()
                 if t["answered"]}
        assert sorted(recon) == sorted(ids), sorted(recon)
        # answered requests carry the net.rpc hop in their timelines
        rpc_tls = [t for t in recon.values()
                   if "net.rpc" in [sp["name"] for sp in t["spans"]]]
        assert rpc_tls, "no timeline carries a net.rpc hop"
        # the hedged request's timeline shows the hedge hop, and the
        # hedge bucket is a named share of the attribution table
        hedged = [t for t in recon.values()
                  if "net.hedge" in [sp["name"] for sp in t["spans"]]]
        assert hedged, "no timeline carries a net.hedge hop"
        assert all(t["buckets"].get("hedge", 0.0) > 0.0 for t in hedged)
        assert doc["rungs"], doc
        assert all("hedge" in r["shares"] for r in doc["rungs"])
    finally:
        for p in (p0, p1):
            if p.poll() is None:
                p.kill()
            p.wait(timeout=30)
