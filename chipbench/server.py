"""The system under test, started inside the benchmark process.

The one departure from `python -m tempo_tpu.cli.main -target=all`: that
entry point blocks on signals, and only the process that holds the chip
can trace it, so the benchmark builds what its `-target=all` branch
builds, in the same order, and keeps the handles. Every request still
crosses HTTP on loopback through the normal handlers.
"""

from __future__ import annotations

import copy
import os
import socket
import threading

import yaml


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def render_config(example_path: str, overrides: dict, run_dir: str) -> str:
    """The shipped example config with the configuration's overrides on
    it, data under `run_dir`, ports free. Returns the YAML's path."""
    from tempo_tpu.cli.config import expand_env

    os.environ["TEMPO_DATA"] = run_dir
    with open(example_path) as f:
        doc = yaml.safe_load(expand_env(f.read())) or {}
    doc = merge(doc, overrides)
    doc.setdefault("server", {})
    doc["server"]["http_port"] = free_port()
    doc["server"]["grpc_port"] = free_port()
    path = os.path.join(run_dir, "config.yaml")
    with open(path, "w") as f:
        yaml.safe_dump(doc, f)
    return path


class Server:
    def __init__(self, config_path: str):
        import jax

        from tempo_tpu.api import HTTPApi, make_grpc_server, serve_http
        from tempo_tpu.cli.config import load_config
        from tempo_tpu.modules import App
        from tempo_tpu.utils.jaxenv import enable_compile_cache

        self.compile_cache = enable_compile_cache()
        self.devices = jax.devices()
        cfg, runtime = load_config(config_path)
        self.app = App(cfg)
        self.app.run_maintenance()
        api = HTTPApi(self.app, multitenancy=runtime["multitenancy"],
                      debug_endpoints=runtime["debug_endpoints"])
        self.http = serve_http(api, host="127.0.0.1",
                               port=runtime["http_port"])
        threading.Thread(target=self.http.serve_forever, daemon=True).start()
        self.grpc = make_grpc_server(
            self.app, f"127.0.0.1:{runtime['grpc_port']}")
        self.grpc.start()
        self.base = f"http://127.0.0.1:{runtime['http_port']}"

    def stop(self) -> None:
        self.grpc.stop(grace=2)
        self.http.shutdown()
        self.http.server_close()
        self.app.shutdown()
