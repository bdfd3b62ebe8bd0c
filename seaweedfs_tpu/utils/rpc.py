"""gRPC without grpcio-tools: generic method registration + client stubs.

The image has grpcio + protoc but not grpcio-tools, so services are declared
in code against protoc-generated message classes. Server side builds a
GenericRpcHandler per service; client side wraps channel.unary_unary etc.
Plays the role of the reference's pb/grpc dial helpers
(weed/operation/grpc_client.go, weed/pb/grpc_client_server.go) including
cached channels.
"""

from __future__ import annotations

import contextlib
import contextvars
import threading
import time
from concurrent import futures
from typing import Callable

import grpc

# -- optional gRPC auth ------------------------------------------------------
# The reference gates its gRPC plane with mTLS from security.toml
# (weed/security/tls.go:26,92). Our equivalent is a shared-key bearer token:
# when a process is configured with the cluster signing key
# (set_cluster_key), every outgoing Stub call attaches a JWT and every
# serve(..., auth_key=...) server verifies it before dispatch. Empty key =
# open cluster, matching the reference default.

_cluster_key: str = ""
_cluster_key_lock = threading.Lock()

# -- optional mTLS -----------------------------------------------------------
# The reference's security.toml [grpc] section configures per-component
# ca/cert/key (weed/security/tls.go:26 NewServerTLS, :92 NewClientTLS);
# here one process-wide TlsConfig covers every serve() and Stub channel.
# Both peers verify each other (require_client_auth) — configure it with
# set_tls_config() before starting servers/clients. None = plaintext.


class TlsConfig:
    def __init__(self, ca_path: str, cert_path: str, key_path: str,
                 server_name: str = "swtpu"):
        self.server_name = server_name
        with open(ca_path, "rb") as f:
            self.ca = f.read()
        with open(cert_path, "rb") as f:
            self.cert = f.read()
        with open(key_path, "rb") as f:
            self.key = f.read()

    def server_credentials(self):
        return grpc.ssl_server_credentials(
            [(self.key, self.cert)], root_certificates=self.ca,
            require_client_auth=True)

    def channel_credentials(self):
        return grpc.ssl_channel_credentials(
            root_certificates=self.ca, private_key=self.key,
            certificate_chain=self.cert)


_tls_config: "TlsConfig | None" = None


def set_tls_config(tls: "TlsConfig | None") -> None:
    """Install process-wide mTLS; drops cached plaintext channels so new
    stubs dial securely."""
    global _tls_config
    with _channel_lock:
        _tls_config = tls
        for ch in _channel_cache.values():
            ch.close()
        _channel_cache.clear()


def tls_enabled() -> bool:
    return _tls_config is not None


def load_tls_from_security_toml() -> "TlsConfig | None":
    """[grpc] ca / cert / key on the config tier chain (tls.go analogue).
    A PARTIAL [grpc] section raises rather than silently running plaintext
    (fail closed — the operator clearly intended TLS)."""
    from . import config as cfg
    ca, cert, key, name = cfg.grpc_tls()
    if not (ca or cert or key):
        return None
    if not (ca and cert and key):
        raise ValueError("security.toml [grpc] must set all of ca/cert/key "
                         "(or none)")
    return TlsConfig(ca, cert, key, server_name=name)


def set_cluster_key(key: str) -> None:
    """Accepts the configured signing key; stores the DERIVED gRPC-plane
    key so control-plane bearer tokens never double as data-plane JWTs."""
    from ..security.jwt import derive_cluster_key
    global _cluster_key
    with _cluster_key_lock:
        _cluster_key = derive_cluster_key(key)


def _outgoing_metadata(span=None) -> list[tuple[str, str]]:
    md = []
    # trace-context propagation: a sampled active span (or `span`, a
    # streaming call's client span, which is never the active one) rides
    # every gRPC hop as traceparent metadata (the HTTP plane uses the
    # header form); unsampled/absent adds nothing to the wire
    from .. import tracing
    tp = tracing.injectable(span)
    if tp:
        md.append((tracing.TRACEPARENT_HEADER, tp))
    # QoS class tag: maintenance-tagged flows (repair executor, rebuild
    # readers) stay maintenance-class across every gRPC hop so remote
    # survivor reads yield to foreground work on the serving node
    from .. import qos
    qc = qos.injectable()
    if qc:
        md.append((qos.QOS_HEADER, qc))
    if not _cluster_key:
        return md
    from ..security.jwt import gen_jwt_for_filer_server
    md.append(("authorization", "Bearer "
               + gen_jwt_for_filer_server(_cluster_key, 60)))
    return md


class _AuthInterceptor(grpc.ServerInterceptor):
    def __init__(self, key: str):
        self._key = key

    def intercept_service(self, continuation, handler_call_details):
        from ..security.jwt import JwtError, decode_jwt
        for k, v in handler_call_details.invocation_metadata or ():
            if k == "authorization" and v.startswith("Bearer "):
                try:
                    decode_jwt(v[7:], self._key)
                    return continuation(handler_call_details)
                except JwtError:
                    break
        # Reject with a handler of the same streaming shape as the target
        # method, else grpc mismatches the wire protocol.
        handler = continuation(handler_call_details)
        if handler is None:
            return None

        def abort(request_or_iter, context):
            context.abort(grpc.StatusCode.UNAUTHENTICATED,
                          "missing or invalid cluster token")

        def abort_stream(request_or_iter, context):
            context.abort(grpc.StatusCode.UNAUTHENTICATED,
                          "missing or invalid cluster token")
            yield  # pragma: no cover

        if handler.unary_unary:
            return grpc.unary_unary_rpc_method_handler(
                abort, handler.request_deserializer,
                handler.response_serializer)
        if handler.unary_stream:
            return grpc.unary_stream_rpc_method_handler(
                abort_stream, handler.request_deserializer,
                handler.response_serializer)
        if handler.stream_unary:
            return grpc.stream_unary_rpc_method_handler(
                abort, handler.request_deserializer,
                handler.response_serializer)
        return grpc.stream_stream_rpc_method_handler(
            abort_stream, handler.request_deserializer,
            handler.response_serializer)


def _extract_trace_context(context):
    """Inbound traceparent metadata -> SpanContext | None."""
    from .. import tracing
    try:
        for k, v in context.invocation_metadata() or ():
            if k == tracing.TRACEPARENT_HEADER:
                return tracing.parse_traceparent(v)
    except Exception:  # noqa: BLE001  # swtpu-lint: disable=silent-except (tracing must never break dispatch)
        pass
    return None


def _extract_qos_class(context) -> str:
    """Inbound x-swtpu-qos metadata -> class name ('' = untagged)."""
    from .. import qos
    try:
        for k, v in context.invocation_metadata() or ():
            if k == qos.QOS_HEADER and v in qos.CLASSES:
                return v
    except Exception:  # noqa: BLE001  # swtpu-lint: disable=silent-except (qos tagging must never break dispatch)
        pass
    return ""


# the handler's place when no profiler annotation is made (no jax)
_NO_ANNOTATION = contextlib.nullcontext()


def _component_of(service: str) -> str:
    # "swtpu.master.Master" -> "master"
    parts = service.split(".")
    return parts[1] if len(parts) > 1 else service


# Server-streaming methods that are SUBSCRIPTIONS, not requests: the
# stream lives for the subscriber's connection lifetime, so a span around
# it would be a giant-duration root that dominates min_ms queries and
# trips the slow-span log on every routine disconnect.
_LONG_LIVED_STREAMS = frozenset({
    "SubscribeMetadata", "SubscribeLocalMetadata", "Subscribe",
    "SubscribeFollowMe", "VolumeTailSender", "KeepConnected",
})


class RpcService:
    """Declarative service: register handlers, then mount on a grpc.Server.

    Unary and bounded server-streaming handlers run inside a tracing
    span (`rpc/<Method>`) parented on the caller's traceparent metadata,
    so a cross-process gRPC hop (master assign/lookup, EC shard reads,
    filer entry RPCs) lands in the same trace as the HTTP hops around
    it; in a process with jax loaded the handler is also the profiler
    annotation `swtpu/rpc.<Method>` (`trace`, `wall_ns`). Long-lived
    connections — bidirectional streams (heartbeats, KeepConnected) and
    the subscription streams in _LONG_LIVED_STREAMS — are not spanned."""

    def __init__(self, name: str):
        self.name = name  # e.g. "swtpu.master.Master"
        self._handlers: dict[str, grpc.RpcMethodHandler] = {}
        self._component = _component_of(name)

    def _traced_unary(self, method: str, fn: Callable) -> Callable:
        from .. import tracing
        from ..tracing.stages import handler_annotation as annotate
        comp = self._component

        def wrapped(request, context):
            from .. import qos as qos_mod
            qc = _extract_qos_class(context)
            token = qos_mod.set_class(qc) if qc else None
            caller = _extract_trace_context(context)
            try:
                with tracing.start_span(f"rpc/{method}", component=comp,
                                        child_of=caller) as sp, \
                        (annotate(method, caller) or _NO_ANNOTATION):
                    try:
                        return fn(request, context)
                    except Exception as e:  # noqa: BLE001 — incl. grpc aborts
                        sp.set_error(e)
                        raise
            finally:
                if token is not None:
                    qos_mod.reset_class(token)
        return wrapped

    def _traced_stream(self, method: str, fn: Callable) -> Callable:
        from .. import tracing
        from ..tracing.stages import handler_annotation as annotate
        comp = self._component

        def wrapped(request, context):
            from .. import qos as qos_mod
            qc = _extract_qos_class(context)
            token = qos_mod.set_class(qc) if qc else None
            caller = _extract_trace_context(context)
            try:
                with tracing.start_span(f"rpc/{method}", component=comp,
                                        child_of=caller) as sp, \
                        (annotate(method, caller) or _NO_ANNOTATION):
                    try:
                        yield from fn(request, context)
                    except GeneratorExit:
                        # client cancelled / stopped consuming: routine
                        # teardown, not a stream failure
                        sp.status = "cancelled"
                        raise
                    except Exception as e:  # noqa: BLE001
                        sp.set_error(e)
                        raise
            finally:
                if token is not None:
                    qos_mod.reset_class(token)
        return wrapped

    def unary(self, method: str, req_cls, resp_cls):
        def deco(fn: Callable):
            self._handlers[method] = grpc.unary_unary_rpc_method_handler(
                self._traced_unary(method, fn),
                request_deserializer=req_cls.FromString,
                response_serializer=resp_cls.SerializeToString)
            return fn
        return deco

    def unary_stream(self, method: str, req_cls, resp_cls):
        def deco(fn: Callable):
            handler = (fn if method in _LONG_LIVED_STREAMS
                       else self._traced_stream(method, fn))
            self._handlers[method] = grpc.unary_stream_rpc_method_handler(
                handler,
                request_deserializer=req_cls.FromString,
                response_serializer=resp_cls.SerializeToString)
            return fn
        return deco

    def stream_unary(self, method: str, req_cls, resp_cls):
        """A client-streaming handler, `fn(request_iterator, context)`:
        bounded by its one reply, so spanned like a unary one."""
        def deco(fn: Callable):
            self._handlers[method] = grpc.stream_unary_rpc_method_handler(
                self._traced_unary(method, fn),
                request_deserializer=req_cls.FromString,
                response_serializer=resp_cls.SerializeToString)
            return fn
        return deco

    def stream_stream(self, method: str, req_cls, resp_cls):
        def deco(fn: Callable):
            self._handlers[method] = grpc.stream_stream_rpc_method_handler(
                fn, request_deserializer=req_cls.FromString,
                response_serializer=resp_cls.SerializeToString)
            return fn
        return deco

    def generic_handler(self) -> grpc.GenericRpcHandler:
        return grpc.method_handlers_generic_handler(self.name, self._handlers)


def serve(bind: str, services: list[RpcService], max_workers: int = 16,
          auth_key: str = "") -> grpc.Server:
    from ..security.jwt import derive_cluster_key
    port = int(bind.rsplit(":", 1)[1])
    if not 0 < port < 65536:
        # grpc silently wraps port numbers modulo 65536, so an overflowed
        # "+10000 convention" port would bind somewhere surprising and
        # clients would talk to the wrong server — fail loudly instead
        raise ValueError(f"invalid port in bind address {bind!r}")
    server = grpc.server(
        # named so the continuous profiler can class these threads grpc
        futures.ThreadPoolExecutor(max_workers=max_workers,
                                   thread_name_prefix="grpc-worker"),
        interceptors=([_AuthInterceptor(derive_cluster_key(auth_key))]
                      if auth_key else []),
        options=[("grpc.max_receive_message_length", 256 << 20),
                 ("grpc.max_send_message_length", 256 << 20)])
    for s in services:
        server.add_generic_rpc_handlers((s.generic_handler(),))
    if _tls_config is not None:
        bound = server.add_secure_port(bind,
                                       _tls_config.server_credentials())
    else:
        bound = server.add_insecure_port(bind)
    if bound == 0:
        # grpc signals bind failure by returning port 0, not raising
        raise OSError(f"failed to bind gRPC server at {bind}")
    server.start()
    return server


_channel_cache: dict[str, grpc.Channel] = {}
_channel_lock = threading.Lock()


def channel(address: str) -> grpc.Channel:
    with _channel_lock:
        ch = _channel_cache.get(address)
        if ch is None:
            opts = [("grpc.max_receive_message_length", 256 << 20),
                    ("grpc.max_send_message_length", 256 << 20)]
            if _tls_config is not None:
                # cluster certs share one CN; targets are raw IPs
                opts.append(("grpc.ssl_target_name_override",
                             _tls_config.server_name))
                ch = grpc.secure_channel(
                    address, _tls_config.channel_credentials(), options=opts)
            else:
                ch = grpc.insecure_channel(address, options=opts)
            _channel_cache[address] = ch
        return ch


def drop_channel(address: str) -> None:
    with _channel_lock:
        ch = _channel_cache.pop(address, None)
    if ch is not None:
        ch.close()


def _client_span(method: str, address: str):
    """The `rpc.client/<Method>` span of one outgoing call made inside a
    trace, and the account its caller runs under (a shell verb's, else
    None); (None, None) outside a trace: a call with no active span
    (heartbeats, pollers) starts no trace of its own, and the
    subscriptions the server side leaves unspanned are left so here."""
    from .. import tracing
    if method in _LONG_LIVED_STREAMS or tracing.current_span() is None:
        return None, None
    sp = tracing.start_span(f"rpc.client/{method}", component="rpc",
                            attrs={"peer": address})
    return sp, tracing.RPC_ACCOUNT.get()


def _end_client_span(sp, acct, method: str, seconds: float) -> None:
    """End a client span and book the call's seconds onto the verb's
    account (summed as calls end, so a verb of thousands of RPCs is not
    cut by the span ring)."""
    sp.end()
    if acct is not None:
        acct.add(method, seconds)


class _SpannedStream:
    """A server stream called inside a trace: iterates and cancels like
    the grpc call it wraps (every other attribute is the call's own), and
    ends the call's client span when the stream is exhausted, fails, is
    cancelled or is dropped. The seconds booked are those spent inside
    `next()` — waiting for the peer — not the consumer's between them."""

    def __init__(self, stream, sp, acct, method: str, seconds: float):
        self._stream = stream
        self._sp = sp
        self._acct = acct
        self._method = method
        self._seconds = seconds
        self._ended = False

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        try:
            item = next(self._stream)
        except BaseException as e:
            self._seconds += time.perf_counter() - t0
            if not isinstance(e, StopIteration):
                self._sp.set_error(e)
            self._end()
            raise
        self._seconds += time.perf_counter() - t0
        return item

    def _end(self) -> None:
        if not self._ended:
            self._ended = True
            _end_client_span(self._sp, self._acct, self._method,
                             self._seconds)

    def cancel(self):
        if not self._ended:
            self._sp.status = "cancelled"
        self._end()
        return self._stream.cancel()

    def __getattr__(self, name: str):
        if name.startswith("_"):  # never the wrapper's own, half-built
            raise AttributeError(name)
        return getattr(self._stream, name)

    def __del__(self):
        if not getattr(self, "_ended", True):  # dropped half-read or unread
            self.cancel()


# The calls of a shell script run for a client in another process
# (shell/executor.py) carry an Event of that client's here: once the
# client has gone, the script's next call raises instead of running on
# under an admin lease that no one is there to give back.
CALL_GATE: "contextvars.ContextVar[threading.Event | None]" = \
    contextvars.ContextVar("swtpu_call_gate", default=None)


class CallerGone(RuntimeError):
    pass


def _check_gate() -> None:
    gate = CALL_GATE.get()
    if gate is not None and gate.is_set():
        raise CallerGone("the shell client that ran this script has gone")


class Stub:
    """Thin client for one service on one address. A call made inside a
    trace runs under a child span `rpc.client/<Method>` (peer as attr)
    whose context is what the server's `rpc/<Method>` span parents on."""

    def __init__(self, address: str, service: str):
        self.address = address
        self.service = service
        self._ch = channel(address)

    def call(self, method: str, request, resp_cls, timeout: float = 30.0):
        _check_gate()
        fn = self._ch.unary_unary(
            f"/{self.service}/{method}",
            request_serializer=type(request).SerializeToString,
            response_deserializer=resp_cls.FromString)
        sp, acct = _client_span(method, self.address)
        if sp is None:
            return fn(request, timeout=timeout,
                      metadata=_outgoing_metadata())
        t0 = time.perf_counter()
        try:
            return fn(request, timeout=timeout,
                      metadata=_outgoing_metadata(sp))
        except BaseException as e:
            sp.set_error(e)
            raise
        finally:
            _end_client_span(sp, acct, method, time.perf_counter() - t0)

    def call_stream(self, method: str, request, resp_cls, timeout: float = 300.0):
        _check_gate()
        fn = self._ch.unary_stream(
            f"/{self.service}/{method}",
            request_serializer=type(request).SerializeToString,
            response_deserializer=resp_cls.FromString)
        sp, acct = _client_span(method, self.address)
        if sp is None:
            return fn(request, timeout=timeout,
                      metadata=_outgoing_metadata())
        t0 = time.perf_counter()
        stream = fn(request, timeout=timeout,
                    metadata=_outgoing_metadata(sp))
        return _SpannedStream(stream, sp, acct, method,
                              time.perf_counter() - t0)

    def stream_unary_future(self, method: str, request_iter, resp_cls,
                            timeout: float = 3600.0):
        """Start a client-streaming call whose requests `request_iter`
        yields already serialized (bytes), and return its future: grpc
        consumes the iterator on a thread of its own, and the call's
        client span ends when the reply (or the error) arrives."""
        _check_gate()
        fn = self._ch.stream_unary(f"/{self.service}/{method}",
                                   request_serializer=None,
                                   response_deserializer=resp_cls.FromString)
        sp, acct = _client_span(method, self.address)
        fut = fn.future(request_iter, timeout=timeout,
                        metadata=_outgoing_metadata(sp))
        if sp is not None:
            t0 = time.perf_counter()

            def done(f):
                if f.cancelled():
                    sp.status = "cancelled"
                elif f.exception() is not None:
                    sp.set_error(f.exception())
                _end_client_span(sp, acct, method, time.perf_counter() - t0)
            fut.add_done_callback(done)
        return fut

    def stream_stream(self, method: str, request_iter, req_cls, resp_cls):
        _check_gate()
        fn = self._ch.stream_stream(
            f"/{self.service}/{method}",
            request_serializer=req_cls.SerializeToString,
            response_deserializer=resp_cls.FromString)
        return fn(request_iter, metadata=_outgoing_metadata())


MASTER_SERVICE = "swtpu.master.Master"
VOLUME_SERVICE = "swtpu.volume.VolumeServer"
FILER_SERVICE = "swtpu.filer.Filer"
