"""Broker semantics: the RabbitMQ behaviors the paper's evaluation relies
on (§4.2/§5.2)."""

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.broker import BrokerCluster, Message


def mk(n_nodes=3, prefetch=4):
    b = BrokerCluster(n_nodes=n_nodes, default_prefetch=prefetch)
    return b


def test_fifo_single_consumer():
    b = mk()
    b.declare_queue("q")
    b.register_consumer("c0", "q", prefetch=100)
    for i in range(10):
        ok, _ = b.publish(Message("q", 100, headers={"i": i}))
        assert ok
    seen = []
    while (d := b.next_delivery("q")) is not None:
        seen.append(d.message.headers["i"])
    assert seen == list(range(10))


def test_round_robin_across_consumers():
    b = mk()
    b.declare_queue("q")
    for c in range(3):
        b.register_consumer(f"c{c}", "q", prefetch=100)
    for i in range(9):
        b.publish(Message("q", 10))
    got = [b.next_delivery("q").consumer_id for _ in range(9)]
    assert got.count("c0") == got.count("c1") == got.count("c2") == 3


def test_prefetch_window_blocks_delivery():
    b = mk()
    b.declare_queue("q")
    b.register_consumer("c0", "q", prefetch=2)
    for _ in range(5):
        b.publish(Message("q", 10))
    d1 = b.next_delivery("q")
    d2 = b.next_delivery("q")
    assert d1 and d2
    assert b.next_delivery("q") is None          # window full
    b.ack("c0", d1.delivery_tag)
    assert b.next_delivery("q") is not None      # window reopened


def test_ack_multiple():
    b = mk()
    b.declare_queue("q")
    ch = b.register_consumer("c0", "q", prefetch=10)
    for _ in range(6):
        b.publish(Message("q", 10))
    tags = [b.next_delivery("q").delivery_tag for _ in range(6)]
    n = b.ack("c0", tags[3], multiple=True)
    assert n == 4
    assert len(ch.unacked) == 2


def test_reject_publish_overflow_and_recovery():
    b = mk()
    b.declare_queue("q", max_bytes=250)
    b.register_consumer("c0", "q", prefetch=10)
    assert b.publish(Message("q", 100))[0]
    assert b.publish(Message("q", 100))[0]
    ok, _ = b.publish(Message("q", 100))          # 300 > 250
    assert not ok
    assert b.queues["q"].stats.rejected == 1
    d = b.next_delivery("q")
    b.ack("c0", d.delivery_tag)
    assert b.publish(Message("q", 100))[0]        # space again


def test_consumer_crash_redelivers_in_order():
    b = mk()
    b.declare_queue("q")
    b.register_consumer("c0", "q", prefetch=10)
    for i in range(4):
        b.publish(Message("q", 10, headers={"i": i}))
    for _ in range(4):
        b.next_delivery("q")
    n = b.consumer_crash("c0")
    assert n == 4
    b.register_consumer("c1", "q", prefetch=10)
    redelivered = [b.next_delivery("q") for _ in range(4)]
    assert [d.message.headers["i"] for d in redelivered] == [0, 1, 2, 3]
    assert all(d.message.redelivered for d in redelivered)


def test_fanout_atomic_and_copies():
    b = mk()
    for c in range(3):
        b.declare_queue(f"bq{c}")
        b.register_consumer(f"c{c}", f"bq{c}", prefetch=10)
    b.declare_fanout("x", [f"bq{c}" for c in range(3)])
    ok, queues = b.publish(Message("fanout:x", 10))
    assert ok and len(queues) == 3
    ids = {b.next_delivery(f"bq{c}").message.msg_id for c in range(3)}
    assert len(ids) == 3                         # distinct copies


def test_fanout_rejects_when_any_queue_full():
    b = mk()
    b.declare_queue("a", max_bytes=1000)
    b.declare_queue("tiny", max_bytes=5)
    b.declare_fanout("x", ["a", "tiny"])
    ok, _ = b.publish(Message("fanout:x", 10))
    assert not ok
    assert len(b.queues["a"]) == 0               # atomic: nothing enqueued


def test_flow_control_thresholds():
    b = mk()
    q = b.declare_queue("q")
    q.FLOW_CREDIT = 400
    b.publish(Message("q", 1, producer_id="p0"))
    assert not q.flow_blocked
    for _ in range(450):
        b.publish(Message("q", 1, producer_id="p0"))
    assert q.flow_blocked
    assert q.flow_threshold == 400               # one publisher


def test_node_failure_and_rehome():
    b = mk()
    b.declare_queue("q0", home_node=0)
    b.declare_queue("q1", home_node=1)
    lost = b.node_failure(0)
    assert lost == ["q0"]
    b.rehome_queue("q0", 2)
    assert b.queues["q0"].home_node == 2


@settings(max_examples=50, deadline=None)
@given(sizes=st.lists(st.integers(1, 1000), min_size=1, max_size=60),
       prefetch=st.integers(1, 8), n_consumers=st.integers(1, 4))
def test_property_conservation_no_loss(sizes, prefetch, n_consumers):
    """Every accepted message is delivered exactly once and acked —
    conservation under arbitrary publish sizes/consumer counts."""
    b = BrokerCluster(default_prefetch=prefetch)
    b.declare_queue("q", max_bytes=10**9)
    for c in range(n_consumers):
        b.register_consumer(f"c{c}", "q", prefetch=prefetch)
    accepted = 0
    for s in sizes:
        ok, _ = b.publish(Message("q", s))
        accepted += int(ok)
    delivered = 0
    while True:
        d = b.next_delivery("q")
        if d is None:
            progressed = False
            for c in range(n_consumers):
                ch = b.channels[f"c{c}"]
                if ch.unacked:
                    tag = max(ch.unacked)
                    b.ack(f"c{c}", tag, multiple=True)
                    progressed = True
            if not progressed:
                break
            continue
        delivered += 1
    assert delivered == accepted
    assert b.total_ready() == 0 and b.total_unacked() == 0
