"""Node-health check workloads, as JAX/host programs.

Reference: dlrover/trainer/torch/node_check/nvidia_gpu.py + utils.py
(``bm_allgather``:82, ``bm_allreduce``:112, ``mock_error``:52) — a matmul +
collective benchmark each node runs under the node-check rendezvous.

TPU translation (SURVEY.md §7 stage 5): the compute probe is a bf16 matmul
on the local chip(s) — it catches a wedged PJRT runtime or a bad chip by
timing MXU work. A chip belongs to one process at a time, so the probe
runs in a short-lived child (``python -m dlrover_tpu.diagnosis.node_check``)
that has exited, and given the chip back, before the agent forks its first
worker; the agent itself never initializes a backend. The network probe is
a **host-to-host TCP transfer over DCN** between pair-group members. DCN
(not ICI) is deliberate: when a bad chip wedges a slice's ICI, per-host DCN
checks still localize the fault (SURVEY.md §7 hard-part (d)). Fault injection via the
``DLROVER_TPU_MOCK_ERR_RANK`` env var mirrors the reference's
``MOCK_ERR_RANK``.
"""

import os
import socket
import struct
import subprocess
import sys
import time
from typing import Dict, Optional

from dlrover_tpu.common.comm import NodeMeta
from dlrover_tpu.common.constants import (
    ConfigKey,
    EnvKey,
    env_float,
    env_str,
)
from dlrover_tpu.common.log import logger


def mock_error(node_rank: int) -> None:
    """Raise if fault injection targets this node (reference utils.py:52)."""
    mock = env_str(EnvKey.MOCK_ERR_RANK) or None
    if mock is not None and int(mock) == node_rank:
        raise RuntimeError(f"mock error on node {node_rank}")


# a cold TPU backend start plus one small compile is ~20-30 s; a wedged
# runtime hangs in backend init forever — this bounds how long the agent
# waits before reporting the node faulty. The partners wait for the
# verdict longer than this (node_check_agent._VERDICT_WAIT_S), so the
# fault is on the books before they stop listening.
DEVICE_CHECK_TIMEOUT_S = 90.0
_RESULT_TAG = "MATMUL_SECONDS"


def matmul_benchmark(size: int = 1024, rounds: int = 4) -> float:
    """Time bf16 matmuls on the local device(s); returns seconds.

    Large square bf16 matmuls tile perfectly onto the MXU, so an anomalous
    time means a sick chip/runtime rather than a bad workload fit.
    Initializes a JAX backend in the CALLING process — the agent reaches
    it only through :func:`matmul_benchmark_in_child`.
    """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _mm(x):
        for _ in range(4):
            x = jnp.matmul(x, x)
            x = x / jnp.max(jnp.abs(x))
        return x

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (size, size), dtype=jnp.bfloat16)
    _mm(x).block_until_ready()  # compile outside the timed region
    start = time.monotonic()
    for _ in range(rounds):
        x = _mm(x)
    x.block_until_ready()
    return time.monotonic() - start


def matmul_benchmark_in_child(
    size: int = 1024, env: Optional[Dict[str, str]] = None,
) -> float:
    """:func:`matmul_benchmark` in a child process that owns the chip
    only for as long as the probe runs. ``env`` is the worker
    environment (the probe must land on the backend the workers will)."""
    proc = subprocess.run(  # noqa: S603
        [sys.executable, "-m", "dlrover_tpu.diagnosis.node_check",
         str(size)],
        env=env, capture_output=True, text=True,
        timeout=DEVICE_CHECK_TIMEOUT_S,
    )
    for line in proc.stdout.splitlines():
        if line.startswith(_RESULT_TAG):
            return float(line.split()[1])
    raise RuntimeError(
        f"device check child failed (rc={proc.returncode}): "
        f"{proc.stderr[-2000:]}"
    )


_LEN = struct.Struct(">Q")


def _send_all(conn: socket.socket, payload: bytes) -> None:
    conn.sendall(_LEN.pack(len(payload)) + payload)


def _recv_all(conn: socket.socket) -> bytes:
    header = b""
    while len(header) < _LEN.size:
        chunk = conn.recv(_LEN.size - len(header))
        if not chunk:
            raise ConnectionError("peer closed")
        header += chunk
    (size,) = _LEN.unpack(header)
    buf = bytearray()
    while len(buf) < size:
        chunk = conn.recv(min(1 << 20, size - len(buf)))
        if not chunk:
            raise ConnectionError("peer closed")
        buf.extend(chunk)
    return bytes(buf)


def tcp_pair_benchmark(
    node_rank: int,
    group: Dict[int, NodeMeta],
    payload_mb: float = 4.0,
    timeout_s: float = 0.0,
    partner_failed=None,
) -> float:
    """All-to-one echo over DCN within a pair group; returns seconds.

    The lowest-ranked member serves on its rendezvous-reported free port;
    every other member streams a payload and reads it back. Both directions
    of each link get exercised, which is what the reference's gloo allgather
    achieves (utils.py:82) without needing a working device fabric.
    """
    ranks = sorted(group)
    if len(ranks) < 2:
        return 0.0
    if not timeout_s:
        # a pair whose partner died pre-connect costs this whole window;
        # chaos/e2e drills shrink it (default matches the reference's
        # 60s gloo store timeout)
        timeout_s = env_float(ConfigKey.CHECK_TIMEOUT_S, 60.0)
    payload = os.urandom(int(payload_mb * 1024 * 1024))
    leader = ranks[0]
    leader_meta = group[leader]
    start = time.monotonic()
    if node_rank == leader:
        server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        server.bind(("", leader_meta.free_port))
        server.listen(len(ranks))
        # short accept slices so a partner whose failure is already on the
        # master's books aborts the wait in ~a poll interval, not the full
        # window (the outcome — this round reports failed — is identical
        # to the timeout's; only the latency differs)
        server.settimeout(1.0)
        served = 0
        deadline = time.monotonic() + timeout_s
        try:
            while served < len(ranks) - 1:
                try:
                    conn, _ = server.accept()
                except socket.timeout:
                    if partner_failed is not None and partner_failed():
                        raise RuntimeError(
                            "pair partner already reported a failed check"
                        )
                    if time.monotonic() > deadline:
                        raise socket.timeout(
                            f"pair partner never connected in {timeout_s}s"
                        )
                    continue
                conn.settimeout(timeout_s)
                data = _recv_all(conn)
                _send_all(conn, data)
                conn.close()
                served += 1
        finally:
            server.close()
    else:
        deadline = time.monotonic() + timeout_s
        conn = None
        # connect-retry kept inline: the abort predicate (partner_failed,
        # polled between attempts) is not expressible as a RetryPolicy
        while conn is None:  # noqa: DLR005
            try:
                conn = socket.create_connection(
                    (leader_meta.host or "127.0.0.1", leader_meta.free_port),
                    timeout=2.0,
                )
            except OSError:
                if partner_failed is not None and partner_failed():
                    raise RuntimeError(
                        "pair partner already reported a failed check"
                    )
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.2)
        conn.settimeout(timeout_s)
        _send_all(conn, payload)
        echoed = _recv_all(conn)
        conn.close()
        if echoed != payload:
            raise RuntimeError("tcp echo payload corrupted")
    return time.monotonic() - start


def run_check_workload(
    node_rank: int,
    group: Dict[int, NodeMeta],
    matmul_size: int = 1024,
    payload_mb: float = 4.0,
    partner_failed=None,
    env: Optional[Dict[str, str]] = None,
) -> float:
    """The full per-node check: fault injection hook → matmul (in a
    child, with the workers' ``env``) → pair DCN echo. Returns total
    elapsed seconds; raises on failure."""
    mock_error(node_rank)
    mm = matmul_benchmark_in_child(size=matmul_size, env=env)
    net = tcp_pair_benchmark(
        node_rank, group, payload_mb=payload_mb,
        partner_failed=partner_failed,
    )
    logger.info(
        "node %s check: matmul=%.3fs net=%.3fs (group=%s)",
        node_rank, mm, net, sorted(group),
    )
    return mm + net


if __name__ == "__main__":
    print(_RESULT_TAG, matmul_benchmark(size=int(sys.argv[1])), flush=True)
