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


# BatchCommands streams one server hosts at once (threads are made as
# streams open): far above any deployment's connection count, so that no
# stream ever queues behind another for its generator's thread
_MAX_STREAMS = 1024


class _HandlerPool(futures.ThreadPoolExecutor):
    """The gRPC handler pool, stamping when gRPC hands it each call:
    ``rpc_accept_wait`` runs from that stamp to the request's
    ``tracker.install`` (the pool's queue, the message receive, the
    wait for the GIL).  The mux's command pool is another of these:
    there the stamp is the stream's feeder handing it a raw command."""

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


def _mux_replying(out):
    """The BatchCommands response serializer: one message of every
    response ``batch_commands`` found ready, and each raw command's
    ``rpc_reply`` (handed over by its worker) closed when the message
    holding its reply is packed."""
    try:
        return wire.pack({"responses": [resp for resp, _handed in out]})
    finally:
        for _resp, handed in out:
            tracker.reply_close(handed)


class _GenericHandler(grpc.GenericRpcHandler):
    """Routes /tikv.Tikv/* to the service: unary by default, plus the
    two streaming surfaces of the reference — coprocessor_stream
    (service/kv.rs:632, server-streamed result pages) and
    batch_commands (service/kv.rs:921, the bidirectional mux)."""

    def __init__(self, prefix: str, dispatch, stream_dispatch=None,
                 batch_dispatch=None, raw_dispatch=None,
                 command_pool=None, stream_pool=None, mux_stats=None):
        self._prefix = prefix
        self._dispatch = dispatch
        self._stream_dispatch = stream_dispatch
        self._batch_dispatch = batch_dispatch
        # the mux's two pools (TikvServer): raw commands run on the
        # first, a hosted stream's response generator parks on the second
        self._command_pool = command_pool
        self._stream_pool = stream_pool
        self._mux_stats = mux_stats
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
                yield from self._batch_dispatch(
                    request_iterator, self._raw_dispatch,
                    self._command_pool)
            # grpcio runs a handler that names a pool on THAT pool
            # (grpc/_server.py _select_thread_pool_for_behavior): a
            # stream's generator, parked for the stream's whole life,
            # takes no worker from the pool the unary RPCs share
            batch.experimental_thread_pool = self._stream_pool
            return grpc.stream_stream_rpc_method_handler(
                batch, request_deserializer=lambda b: b,
                response_serializer=_mux_replying)

        if method in self._raw_dispatch:
            fn = self._raw_dispatch[method]

            def raw_unary(raw: bytes, ctx, fn=fn):
                if self._mux_stats is not None:
                    for key, _value in ctx.invocation_metadata():
                        if key == wire.MUX_RESEND_KEY:
                            self._mux_stats.note(unary_resends=1)
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
        # the mux (service.batch_commands): raw commands of every hosted
        # BatchCommands stream run on ONE pool of the handler pool's
        # width, stamped as the handler pool stamps a call, so the store
        # admits as many cop tasks at once over the mux as over unary
        # calls; a stream's response generator parks on a pool of its
        # own (a thread a live stream, made when the stream opens) and
        # its feeder on a thread of its own, so neither takes a worker
        # from the commands nor from the unary RPCs
        self._command_pool = _HandlerPool(max_workers=max_workers,
                                          thread_name_prefix="mux-command")
        self._stream_pool = futures.ThreadPoolExecutor(
            max_workers=_MAX_STREAMS, thread_name_prefix="mux-stream")
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
                },
                command_pool=self._command_pool,
                stream_pool=self._stream_pool,
                mux_stats=self.service.mux_stats),))
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
        for pool in (self._pool, self._command_pool, self._stream_pool):
            pool.shutdown(wait=True)

    def wait(self) -> None:
        self._server.wait_for_termination()
