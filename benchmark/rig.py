"""The store under test, in this process: Node + TikvServer with a
DeviceRunner built as ``server/cli.py`` builds it from the
configuration's TOML.  The caller is the one process that touches JAX.
PD is a process of its own (``python -m tikv_tpu.server pd``, the
README's entry point), as in every deployment: a TSO fetch does not
take the store's GIL."""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import time


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def listening(port: int) -> bool:
    with socket.socket() as s:
        s.settimeout(0.2)
        return s.connect_ex(("127.0.0.1", port)) == 0


class Rig:
    def __init__(self, toml_path: str, root: str,
                 row_threshold: int | None = None):
        import jax

        from tikv_tpu.config import TikvConfig
        from tikv_tpu.device import DeviceRunner
        from tikv_tpu.parallel import make_mesh, parse_mesh_shape
        from tikv_tpu.raftstore.metapb import Store
        from tikv_tpu.server import Node, RemotePdClient, TikvServer

        self.server = None
        pd_port = free_port()
        self.pd_addr = f"127.0.0.1:{pd_port}"
        # its stdout is not ours: the result line stays the last.  PD
        # imports no JAX; should it ever, it must not take the chip
        self.pd_proc = subprocess.Popen(
            [sys.executable, "-m", "tikv_tpu.server", "pd",
             "--addr", self.pd_addr],
            cwd=root, stdin=subprocess.DEVNULL, stdout=sys.stderr,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        try:
            config = TikvConfig.from_file(toml_path)
            if row_threshold is not None:       # dry run at a toy size only
                config.coprocessor.device_row_threshold = row_threshold
            cc = config.coprocessor
            self.runner = DeviceRunner(
                mesh=make_mesh(shape=parse_mesh_shape(cc.mesh_shape)),
                placement=cc.device_placement,
                placement_rows=cc.placement_rows,
                slice_trip_strikes=cc.slice_trip_strikes,
                slice_probe_cooldown_s=cc.slice_probe_cooldown_s,
                slice_latency_outlier_s=cc.slice_latency_outlier_s,
                flight_recorder_depth=cc.flight_recorder_depth)
            self.devices = jax.devices()
            deadline = time.monotonic() + 60
            while not listening(pd_port):
                if self.pd_proc.poll() is not None or \
                        time.monotonic() > deadline:
                    raise RuntimeError(f"pd did not come up (exit code "
                                       f"{self.pd_proc.poll()})")
                time.sleep(0.05)
            self.node = Node("127.0.0.1:0", RemotePdClient(self.pd_addr),
                             device_runner=self.runner, config=config)
            self.server = TikvServer(self.node, status_addr="127.0.0.1:0")
            self.node.addr = f"127.0.0.1:{self.server.port}"
            self.node.pd.put_store(Store(self.node.store_id, self.node.addr))
            self.server.start()
            self.status_port = self.server.status_server.port
        except BaseException:
            self.stop()
            raise

    def memory_peak_bytes(self) -> int:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks)

    def stop(self) -> None:
        if self.server is not None:
            self.server.stop()
            self.server = None
        if self.pd_proc is not None:
            self.pd_proc.terminate()
            try:
                self.pd_proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.pd_proc.kill()
                self.pd_proc.wait()
            self.pd_proc = None
