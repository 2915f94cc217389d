"""Echo/decode server + client (paper §7.3): model tokens served over UDP
with GENESYS network syscalls.

  PYTHONPATH=src python examples/serve_echo.py
"""
import socket
import threading

import numpy as np

from repro.configs import get_config
from repro.core.genesys import Genesys, GenesysConfig
from repro.launch.serve import load_model
from repro.models.registry import get_api
from repro.serving.server import GenesysUdpServer

g = Genesys(GenesysConfig(n_workers=2))
model = load_model(get_config("rwkv6-3b").reduced())
cache = get_api(model.cfg).init_cache(model.cfg, 1, 128)

srv = GenesysUdpServer(g, port=0, payload=512)
port = g.table._sockets[srv.fd].getsockname()[1]

client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
client.bind(("127.0.0.1", 0))
client.settimeout(30)
cport = client.getsockname()[1]

with model.mesh:
    th = threading.Thread(
        target=srv.serve_model,
        args=(model.serve_step, model.params, cache),
        kwargs=dict(n_batches=1, reply_port=cport, max_tokens=6),
        daemon=True)
    th.start()
    prompt = np.array([1, 5, 9], dtype=np.int32)
    client.sendto(prompt.tobytes(), ("127.0.0.1", port))
    data, _ = client.recvfrom(512)
    th.join(30)

tokens = np.frombuffer(data, dtype=np.int32)
print(f"prompt {prompt.tolist()} -> decoded continuation {tokens.tolist()}")
print(f"server stats: {srv.stats}")
srv.close()
g.shutdown()
