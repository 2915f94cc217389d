"""The open-loop client, run as a child process that never imports JAX:
it shares no interpreter lock with the server it loads.

  python loadgen.py SCHEDULE.npz SERVER_PORT OUT.json

It binds its reply socket, prints ``port <n>`` on stdout, and waits for
one line on stdin: ``go <t0> <deadline>``, two ``time.monotonic()``
readings (the clock is system-wide, so parent and child share it). Each
request is sent at ``t0 + due`` whatever has come back, and each reply is
stamped as it arrives. It stops when every reply is in or at the
deadline, and writes one JSON object to OUT.json: per request the time it
was sent and the time its reply came, both relative to ``t0``, and the
tokens it carried.
"""
from __future__ import annotations

import json
import select
import socket
import sys
import time

import numpy as np

from schedule import Schedule


def run(sched: Schedule, server_port: int, sock: socket.socket,
        t0: float, deadline: float) -> dict:
    n = len(sched)
    payloads = [sched.payload(i) for i in range(n)]
    sent = [None] * n
    recv = [None] * n
    tokens = [None] * n
    nxt, got = 0, 0
    dest = ("127.0.0.1", server_port)
    while got < n:
        now = time.monotonic()
        if now >= deadline:
            break
        while nxt < n and t0 + sched.due_s[nxt] <= now:
            sock.sendto(payloads[nxt], dest)
            sent[nxt] = time.monotonic() - t0
            nxt += 1
        wake = t0 + sched.due_s[nxt] if nxt < n else deadline
        wait = max(0.0, min(wake, deadline) - time.monotonic())
        ready, _, _ = select.select([sock], [], [], wait)
        while ready:
            data = sock.recv(65536)
            t = time.monotonic() - t0
            words = np.frombuffer(data, np.int32)
            tag = int(words[0]) if len(words) else -1
            if 0 <= tag < n and recv[tag] is None:
                recv[tag] = t
                tokens[tag] = words[1:].tolist()
                got += 1
            ready, _, _ = select.select([sock], [], [], 0)
    late = [s - float(d) for s, d in zip(sent, sched.due_s) if s is not None]
    return {"sent_s": sent, "recv_s": recv, "tokens": tokens,
            "late_p50_ms": 1e3 * float(np.median(late)) if late else None,
            "late_max_ms": 1e3 * float(max(late)) if late else None}


def main(argv: list[str]) -> int:
    sched_path, server_port, out_path = argv[0], int(argv[1]), argv[2]
    sched = Schedule.load(sched_path)
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 22)
    sock.bind(("127.0.0.1", 0))
    print(f"port {sock.getsockname()[1]}", flush=True)
    word, t0, deadline = sys.stdin.readline().split()
    assert word == "go", word
    try:
        res = run(sched, server_port, sock, float(t0), float(deadline))
    finally:
        sock.close()
    with open(out_path, "w") as f:
        json.dump(res, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
