"""One process for each chip: what ``dl4j-tpu fleet`` hands its children.

A TPU chip serves one process at a time. The fleet parent stays off jax
and starts every ``serve`` child seeing only its own chip; asking for
more replicas than chips is an error at boot, not a hang. No chip is
needed to check the bookkeeping: the chip list and the child process are
faked.
"""

import pytest

from deeplearning4j_tpu.cli import driver
from deeplearning4j_tpu.serving import replica_proc
from deeplearning4j_tpu.util import chips as chips_mod


class _FakeReplica:
    spawned = []

    def __init__(self, argv, replica_id, port, ready_pattern="READY",
                 env=None, **_):
        self.argv, self.env = list(argv), env
        self.replica_id, self.port = replica_id, port
        self.address = f"127.0.0.1:{port}"
        self.alive = True
        _FakeReplica.spawned.append(self)

    def wait_ready(self, timeout_s=0.0):
        pass

    def shutdown(self):
        self.alive = False

    sigkill = shutdown


@pytest.fixture
def fleet(monkeypatch):
    _FakeReplica.spawned = []
    monkeypatch.setattr(replica_proc, "ReplicaProcess", _FakeReplica)

    def build(chips, *argv):
        monkeypatch.setattr(chips_mod, "local_tpu_chips", lambda: chips)
        args = driver.build_parser().parse_args(
            ["fleet", "--model", "m.zip", "--port", "0", *argv])
        seeds, router, controller = driver.fleet_from_args(args)
        return args, seeds, controller

    return build


def _visible(proc):
    return proc.env and proc.env["TPU_VISIBLE_CHIPS"]


def test_each_child_sees_exactly_its_own_chip(fleet):
    args, seeds, controller = fleet([0, 1, 2, 3], "--replicas", "4")
    assert [_visible(p) for p in seeds] == ["0", "1", "2", "3"]
    for p in seeds:
        assert p.env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert p.env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    # every chip is held: a fifth replica has nowhere to go...
    with pytest.raises(RuntimeError, match="no free TPU chip"):
        controller.replica_factory("extra")
    # ...until one dies, and then it gets exactly that chip
    seeds[2].shutdown()
    assert _visible(controller.replica_factory("extra")) == "2"


def test_more_replicas_than_chips_is_an_error_at_boot(fleet):
    with pytest.raises(ValueError, match="does not fit"):
        fleet([0, 1, 2, 3], "--replicas", "5", "--max-replicas", "8")
    assert _FakeReplica.spawned == []  # refused before any child


def test_autoscaling_ceiling_is_capped_at_the_chip_count(fleet):
    _, _, controller = fleet([0, 1], "--replicas", "1",
                             "--max-replicas", "4")
    assert controller.max_replicas == 2


def test_a_tp_replica_takes_the_whole_host(fleet):
    _, seeds, _ = fleet([0, 1, 2, 3], "--replicas", "1", "--tp", "4",
                        "--max-replicas", "1")
    assert seeds[0].env is None  # sees every chip, as its parent would
    with pytest.raises(ValueError, match="does not fit"):
        fleet([0, 1, 2, 3], "--replicas", "2", "--tp", "2")


def test_a_host_without_a_tpu_leaves_the_environment_alone(fleet):
    _, seeds, _ = fleet([], "--replicas", "3")
    assert [p.env for p in seeds] == [None, None, None]
