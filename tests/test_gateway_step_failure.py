"""A failure must look like a failure.

The gateway's stepping thread is the only source of progress. Before
this gate, an exception out of ``engine.step()`` — a kernel the compiler
refuses, an out-of-memory — killed that daemon thread silently:
``/v1/healthz`` kept answering ok, clients blocked until their own
timeouts, ``dl4j-tpu serve`` slept on and would exit 0, and a fleet
child's traceback went to DEVNULL. Each of those is checked here.
"""

import sys
import threading
import time

import pytest

from deeplearning4j_tpu.cli import driver
from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving import (
    DecodeEngine,
    GatewayClient,
    GatewayError,
    ServingGateway,
)
from deeplearning4j_tpu.serving.replica_proc import (
    ReplicaProcess,
    free_port,
)

V = 12
BOOM = "Mosaic refused the kernel"


def _failing_gateway() -> ServingGateway:
    """A gateway whose engine raises at its first ``step()``."""
    net = MultiLayerNetwork(transformer_lm(
        n_in=V, width=32, n_layers=1, n_heads=2, n_classes=V,
        seed=7)).init()
    eng = DecodeEngine(net, n_slots=2)

    def step(results=None):
        raise RuntimeError(BOOM)

    eng.step = step
    return ServingGateway(eng, keepalive_s=0.05)


def test_step_failure_reaches_every_client_and_healthz():
    gw = _failing_gateway()
    gw._paused = True  # park both requests in flight before any step
    gw.start()
    try:
        client = GatewayClient(gw.address, timeout_s=20.0)
        assert client.healthz()["ok"]
        outcomes = {}

        def blocking():
            try:
                outcomes["blocking"] = client.generate([1, 2, 3], 4)
            except GatewayError as e:
                outcomes["blocking"] = e

        def streaming():
            s = client.stream([4, 5], 4)
            outcomes["deltas"] = [d for d in s]
            outcomes["streaming"] = s.result

        threads = [threading.Thread(target=blocking),
                   threading.Thread(target=streaming)]
        for t in threads:
            t.start()
        t0 = time.monotonic()
        while len(gw._live) < 2:  # both requests parked in flight
            assert time.monotonic() - t0 < 20.0
            time.sleep(0.01)
        with gw._wake:
            gw._paused = False
            gw._wake.notify_all()
        for t in threads:
            t.join(timeout=20.0)
            assert not t.is_alive(), "a client hung on a dead stepper"

        err = outcomes["blocking"]
        assert isinstance(err, GatewayError) and err.status == 500
        assert BOOM in err.payload["error"]
        done = outcomes["streaming"]
        assert done["done"] and done["status"] == 500
        assert done["finish_reason"] == "fault"
        assert BOOM in done["error"] and outcomes["deltas"] == []

        # new work is refused with the same error, not queued forever
        with pytest.raises(GatewayError) as ei:
            client.generate([7], 2)
        assert ei.value.status == 500 and BOOM in ei.value.payload["error"]

        health = client.healthz()
        assert health["ok"] is False and health["state"] == "failed"
        assert BOOM in health["error"]
        assert gw.failure == f"RuntimeError: {BOOM}"
        assert not gw._stepper.is_alive()
    finally:
        gw.close()


def test_serve_command_exits_nonzero_when_the_stepper_dies(
        monkeypatch, capsys):
    gw = _failing_gateway()
    monkeypatch.setattr(driver, "gateway_from_args", lambda args: gw)

    def poke():
        client = GatewayClient(gw.address, timeout_s=20.0)
        with pytest.raises(GatewayError):
            client.generate([1, 2], 2)

    poker = threading.Timer(0.2, poke)
    poker.start()
    args = driver.build_parser().parse_args(
        ["serve", "--model", "unused.zip", "--port", "0"])
    rc = driver._cmd_serve(args)
    poker.join(timeout=20.0)
    assert rc == 1
    out = capsys.readouterr()
    assert "serving on" in out.out and "device" in out.out
    assert BOOM in out.err


def test_replica_that_dies_at_boot_reports_its_stderr(capfd):
    child = ("import sys; sys.stderr.write('child: no chip for me\\n'); "
             "sys.exit(3)")
    proc = ReplicaProcess([sys.executable, "-c", child],
                          replica_id="r0", port=free_port())
    try:
        with pytest.raises(RuntimeError) as ei:
            proc.wait_ready(timeout_s=30.0)
    finally:
        proc.shutdown()
    assert "exited with code 3" in str(ei.value)
    # the child's own words are on this process's stderr, not discarded
    assert "child: no chip for me" in capfd.readouterr().err
