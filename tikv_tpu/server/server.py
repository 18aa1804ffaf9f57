"""gRPC server assembly.

Reference: src/server/server.rs (grpcio Server build_and_bind :288) and
components/server/src/server.rs service registration (:1122-1296).
Methods are registered generically under ``/tikv.Tikv/<Method>`` with
msgpack bodies (wire.py).
"""

from __future__ import annotations

import time
from concurrent import futures
from typing import Optional

import grpc

from ..utils import tracker
from . import wire
from .node import Node
from .service import KvService


class _HandlerPool(futures.ThreadPoolExecutor):
    """The gRPC handler pool, stamping when gRPC hands it each call:
    ``rpc_accept_wait`` runs from that stamp to the request's
    ``tracker.install`` (the pool's queue, the message receive, the
    wait for the GIL)."""

    def submit(self, fn, /, *args, **kwargs):
        return super().submit(_stamped, time.perf_counter_ns(), fn,
                              *args, **kwargs)


def _stamped(submit_ns: int, fn, *args, **kwargs):
    tracker.rpc_task_begin(submit_ns)
    try:
        return fn(*args, **kwargs)
    finally:
        tracker.rpc_task_end()


def _replying(pack):
    """A unary response serializer that closes ``rpc_reply`` (opened
    where ``_seal_traced`` froze the trace) when the pack returns."""
    def serialize(resp):
        try:
            return pack(resp)
        finally:
            tracker.reply_done()
    return serialize


class _GenericHandler(grpc.GenericRpcHandler):
    """Routes /tikv.Tikv/* to the service: unary by default, plus the
    two streaming surfaces of the reference — coprocessor_stream
    (service/kv.rs:632, server-streamed result pages) and
    batch_commands (service/kv.rs:921, the bidirectional mux)."""

    def __init__(self, prefix: str, dispatch, stream_dispatch=None,
                 batch_dispatch=None, raw_dispatch=None):
        self._prefix = prefix
        self._dispatch = dispatch
        self._stream_dispatch = stream_dispatch
        self._batch_dispatch = batch_dispatch
        # methods served from RAW wire bytes (no eager unpack): the
        # coprocessor fast path template-matches the bytes and only
        # falls back to a full decode on a miss; responses may come
        # back pre-packed (wire.pack_response passes bytes through)
        self._raw_dispatch = raw_dispatch or {}

    def service(self, handler_call_details):
        name = handler_call_details.method
        if not name.startswith(self._prefix):
            return None
        method = name[len(self._prefix):]

        if self._stream_dispatch is not None and \
                method in self._stream_dispatch:
            fn = self._stream_dispatch[method]

            def stream(req: dict, ctx, fn=fn):
                yield from fn(req, ctx)
            return grpc.unary_stream_rpc_method_handler(
                stream, request_deserializer=wire.unpack,
                response_serializer=wire.pack)

        if method == "BatchCommands" and self._batch_dispatch is not None:
            def batch(request_iterator, ctx):
                yield from self._batch_dispatch(request_iterator)
            return grpc.stream_stream_rpc_method_handler(
                batch, request_deserializer=wire.unpack,
                response_serializer=wire.pack)

        if method in self._raw_dispatch:
            fn = self._raw_dispatch[method]

            def raw_unary(raw: bytes, ctx, fn=fn):
                return fn(method, raw)
            return grpc.unary_unary_rpc_method_handler(
                raw_unary, request_deserializer=lambda b: b,
                response_serializer=_replying(wire.pack_response))

        def unary(req: dict, ctx) -> dict:
            return self._dispatch(method, req)

        return grpc.unary_unary_rpc_method_handler(
            unary, request_deserializer=wire.unpack,
            response_serializer=_replying(wire.pack))


class TikvServer:
    """One listening tikv-server process."""

    def __init__(self, node: Node, max_workers: int = 8,
                 status_addr: Optional[str] = None):
        self.node = node
        self._stopped = False
        self.service = KvService(node)
        # keep the handler pool so stop() can JOIN its (non-daemon)
        # workers — grpc's stop() alone leaves them parked on the work
        # queue until the executor is garbage collected, which leaks a
        # thread per in-process server cycle (chaos restarts, tests)
        # (named for /health tracing.threads: role rpc_handler)
        self._pool = _HandlerPool(max_workers=max_workers,
                                  thread_name_prefix="rpc-handler")
        self._server = grpc.server(self._pool)
        self._server.add_generic_rpc_handlers((
            _GenericHandler(
                "/tikv.Tikv/", self.service.handle,
                stream_dispatch={
                    "CoprocessorStream": self.service.copr_stream_rpc,
                    "Cdc": self.service.cdc_stream,
                    "Backup": self.service.backup_stream,
                },
                batch_dispatch=self.service.batch_commands,
                raw_dispatch={
                    "Coprocessor": self.service.handle_raw,
                }),))
        from .security import bind_port
        self.port = bind_port(self._server, node.addr)
        assert self.port, f"cannot bind {node.addr}"
        # HTTP status server (/metrics, /config, /status —
        # status_server/mod.rs), bound from config or the explicit arg
        self.status_server = None
        saddr = status_addr or getattr(node, "config", None) and \
            node.config.server.status_addr
        if saddr:
            from .status_server import StatusServer
            self.status_server = StatusServer(
                saddr, node=node,
                config_controller=node.config_controller)

    def start(self) -> None:
        self._stopped = False
        self.node.start()
        self._server.start()
        if self.status_server is not None:
            self.status_server.start()

    def stop(self, grace: Optional[float] = 0.5) -> None:
        self._stopped = True    # service_event dispatcher exits on this
        if self.status_server is not None:
            self.status_server.stop()
        # wait out the grace so in-flight handlers finish before the
        # node (and its pools) tear down under them, then join the
        # handler workers — stop-under-load must leave no threads
        self._server.stop(grace).wait()
        self.node.stop()
        self._pool.shutdown(wait=True)

    def wait(self) -> None:
        self._server.wait_for_termination()
