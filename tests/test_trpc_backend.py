"""TRPC backend: tensor-native TCP transport.

Reference parity target: ``communication/trpc/trpc_comm_manager.py:21``
(torch.rpc with CUDA-RPC tensor-native transfers). Covers the raw frame
codec (bf16 bit-exactness), a two-manager exchange, and a full cross-silo
round over the backend.
"""

import socket
import threading

import numpy as np
import pytest

import fedml_tpu as fedml
from fedml_tpu.core.distributed.communication.message import Message
from fedml_tpu.core.distributed.communication.trpc.trpc_comm_manager import (
    TRPCCommManager,
    encode_frame,
    recv_frame,
)


def _send_over_socketpair(msg: Message) -> Message:
    a, b = socket.socketpair()
    try:
        header, tensors = encode_frame(msg)
        a.sendall(header)
        for t in tensors:
            a.sendall(memoryview(t).cast("B"))
        return recv_frame(b)
    finally:
        a.close()
        b.close()


def test_frame_roundtrip_bf16_exact():
    import jax.numpy as jnp

    msg = Message(5, 2, 0)
    msg.add_params("num_samples", 17)
    w = jnp.asarray(np.random.default_rng(0).normal(size=(8, 4)), jnp.bfloat16)
    params = {"layer": {"w": w, "b": jnp.arange(4, dtype=jnp.float32)}, "extra": (jnp.ones(2), None)}
    msg.add_params(Message.MSG_ARG_KEY_MODEL_PARAMS, params)

    back = _send_over_socketpair(msg)
    assert back.get_type() == 5 and back.get_sender_id() == 2
    assert back.get("num_samples") == 17
    got = back.get(Message.MSG_ARG_KEY_MODEL_PARAMS)
    assert got["layer"]["w"].dtype.name == "bfloat16"
    # bit-exact: bf16 travels as raw uint16 bits, no float round-trip
    np.testing.assert_array_equal(
        np.asarray(w).view(np.uint16), got["layer"]["w"].view(np.uint16)
    )
    np.testing.assert_array_equal(np.asarray(got["layer"]["b"]), np.arange(4, dtype=np.float32))
    assert got["extra"][1] is None


def test_frame_no_payload():
    msg = Message(1, 0, 3)
    back = _send_over_socketpair(msg)
    assert back.get_type() == 1 and back.get_receiver_id() == 3
    assert back.get(Message.MSG_ARG_KEY_MODEL_PARAMS) is None


def test_two_manager_exchange():
    base = 29110
    m0 = TRPCCommManager(client_id=0, client_num=1, base_port=base)
    m1 = TRPCCommManager(client_id=1, client_num=1, base_port=base)
    got = {}

    class Obs:
        def __init__(self, key):
            self.key = key

        def receive_message(self, msg_type, msg):
            got[self.key] = msg

    m0.add_observer(Obs("m0"))
    m1.add_observer(Obs("m1"))
    t0 = threading.Thread(target=m0.handle_receive_message, daemon=True)
    t1 = threading.Thread(target=m1.handle_receive_message, daemon=True)
    t0.start()
    t1.start()
    try:
        msg = Message(7, 0, 1)
        msg.add_params(Message.MSG_ARG_KEY_MODEL_PARAMS, {"x": np.full((1024,), 3.0, np.float32)})
        m0.send_message(msg)
        reply = Message(8, 1, 0)
        reply.add_params(Message.MSG_ARG_KEY_MODEL_PARAMS, {"x": np.full((1024,), 4.0, np.float32)})
        m1.send_message(reply)
        import time

        deadline = time.time() + 30
        while time.time() < deadline and ("m0" not in got or "m1" not in got):
            time.sleep(0.05)
        assert got["m1"].get_type() == 7
        np.testing.assert_allclose(got["m1"].get(Message.MSG_ARG_KEY_MODEL_PARAMS)["x"], 3.0)
        assert got["m0"].get_type() == 8
        np.testing.assert_allclose(got["m0"].get(Message.MSG_ARG_KEY_MODEL_PARAMS)["x"], 4.0)
    finally:
        m0.stop_receive_message()
        m1.stop_receive_message()
        t0.join(timeout=10)
        t1.join(timeout=10)


def test_send_survives_peer_restart():
    """Dead cached socket is dropped and the send retried on a fresh
    connection (elastic restarts: the peer's listener comes back on the
    same port)."""
    import time

    base = 29150
    m0 = TRPCCommManager(client_id=0, client_num=1, base_port=base)
    m1 = TRPCCommManager(client_id=1, client_num=1, base_port=base)
    got = []

    class Obs:
        def receive_message(self, msg_type, msg):
            got.append(msg_type)

    try:
        m1.add_observer(Obs())
        t1 = threading.Thread(target=m1.handle_receive_message, daemon=True)
        t1.start()
        m0.send_message(Message(1, 0, 1))
        # the first message is IN before the peer goes: torn down with the
        # connection still unaccepted (6 xdist workers), the cached socket is
        # not dead yet and takes the second send without an error
        deadline = time.time() + 30
        while time.time() < deadline and 1 not in got:
            time.sleep(0.05)
        assert 1 in got
        # peer "restarts": old manager torn down, new one on the same port
        m1.stop_receive_message()
        t1.join(timeout=10)
        m1b = TRPCCommManager(client_id=1, client_num=1, base_port=base)
        m1b.add_observer(Obs())
        t1b = threading.Thread(target=m1b.handle_receive_message, daemon=True)
        t1b.start()
        try:
            msg = Message(2, 0, 1)
            msg.add_params(Message.MSG_ARG_KEY_MODEL_PARAMS, {"x": np.ones(16, np.float32)})
            m0.send_message(msg)  # cached socket is dead -> must reconnect
            deadline = time.time() + 30
            while time.time() < deadline and 2 not in got:
                time.sleep(0.05)
            assert 2 in got
        finally:
            m1b.stop_receive_message()
            t1b.join(timeout=10)
    finally:
        m0.stop_receive_message()


def _make_args(run_id, rank, role, n_clients=2, rounds=2):
    from fedml_tpu.arguments import default_config

    return default_config(
        "cross_silo",
        run_id=run_id,
        rank=rank,
        role=role,
        backend="TRPC",
        scenario="horizontal",
        client_num_in_total=n_clients,
        client_num_per_round=n_clients,
        comm_round=rounds,
        epochs=1,
        batch_size=16,
        frequency_of_the_test=1,
        dataset="synthetic",
        model="lr",
        random_seed=0,
    )


def _run_party(args, results, key):
    args = fedml.init(args)
    device = fedml.device.get_device(args)
    dataset, output_dim = fedml.data.load(args)
    model = fedml.model.create(args, output_dim)
    runner = fedml.FedMLRunner(args, device, dataset, model)
    results[key] = runner.run()


@pytest.mark.slow
def test_cross_silo_over_trpc():
    run_id = "trpc_cs_1"
    n_clients, rounds = 2, 2
    results = {}
    threads = [
        threading.Thread(
            target=_run_party, args=(_make_args(run_id, 0, "server"), results, "server"), daemon=True
        )
    ]
    for rank in range(1, n_clients + 1):
        threads.append(
            threading.Thread(
                target=_run_party,
                args=(_make_args(run_id, rank, "client"), results, f"client{rank}"),
                daemon=True,
            )
        )
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
        assert not t.is_alive(), "cross-silo-over-TRPC run deadlocked"
    metrics = results["server"]
    assert metrics is not None and "test_acc" in metrics
    assert np.isfinite(metrics["test_loss"])
