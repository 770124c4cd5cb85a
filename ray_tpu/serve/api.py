"""Public Serve API: @deployment / .bind() / run() / handles.

Reference: python/ray/serve/api.py (serve.run, @serve.deployment),
deployment.py (Deployment/Application), handle.py:692 (DeploymentHandle,
.remote :768).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, Optional

import cloudpickle

import ray_tpu
from ._private.controller import CONTROLLER_NAME, ServeController
from ._private.router import Router

_proxy = None          # ProxyActor handle (one per serve.start with http)
_grpc_proxy = None     # GrpcProxyActor handle
_http_port: Optional[int] = None
_routes: Dict[str, str] = {}


@dataclasses.dataclass
class Application:
    """A deployment bound to its init args (reference: Application from
    Deployment.bind)."""
    deployment: "Deployment"
    init_args: tuple
    init_kwargs: dict


class Deployment:
    def __init__(self, target: Callable, name: str, num_replicas: int = 1,
                 ray_actor_options: Optional[dict] = None,
                 route_prefix: str = "/",
                 autoscaling_config: Optional[dict] = None):
        self._target = target
        self.name = name
        self.num_replicas = num_replicas
        self.ray_actor_options = ray_actor_options or {}
        self.route_prefix = route_prefix
        # {"min_replicas", "max_replicas", "target_ongoing_requests",
        #  "upscale_delay_s", "downscale_delay_s"} (reference:
        #  serve AutoscalingConfig, autoscaling_policy.py)
        self.autoscaling_config = autoscaling_config

    def options(self, *, name: Optional[str] = None,
                num_replicas: Optional[int] = None,
                ray_actor_options: Optional[dict] = None,
                route_prefix: Optional[str] = None,
                autoscaling_config: Optional[dict] = None) -> "Deployment":
        return Deployment(
            self._target,
            name=self.name if name is None else name,
            num_replicas=(self.num_replicas if num_replicas is None
                          else num_replicas),
            ray_actor_options=(self.ray_actor_options
                               if ray_actor_options is None
                               else ray_actor_options),
            route_prefix=(self.route_prefix if route_prefix is None
                          else route_prefix),
            autoscaling_config=(self.autoscaling_config
                                if autoscaling_config is None
                                else autoscaling_config))

    def bind(self, *args, **kwargs) -> Application:
        return Application(self, args, kwargs)

    def __call__(self, *a, **k):
        raise TypeError(
            f"deployment {self.name} must be deployed with serve.run("
            f"{self.name}.bind(...)) and called through a handle")


def deployment(_target: Callable = None, *, name: Optional[str] = None,
               num_replicas: int = 1,
               ray_actor_options: Optional[dict] = None,
               route_prefix: str = "/",
               autoscaling_config: Optional[dict] = None):
    """@serve.deployment decorator (reference: serve/api.py)."""
    def deco(target):
        return Deployment(target, name or target.__name__,
                          num_replicas=num_replicas,
                          ray_actor_options=ray_actor_options,
                          route_prefix=route_prefix,
                          autoscaling_config=autoscaling_config)
    if _target is not None:
        return deco(_target)
    return deco


_DEATH_RETRIES = 2


class DeploymentResponse:
    """Future-like result of handle.remote() (reference:
    handle.DeploymentResponse).  Sync contexts wrap an ObjectRef;
    async contexts (a deployment calling another deployment) wrap an
    eagerly-scheduled asyncio.Task that resolves to the final value.

    Replica death is retried transparently (reference: the Serve router
    reassigns requests that failed because their replica actor died —
    user exceptions are NOT retried): `retry` re-invalidates the routing
    table and dispatches to another replica, bounded at _DEATH_RETRIES."""

    def __init__(self, ref=None, task=None, retry=None, origin=None):
        self._ref = ref
        self._task = task
        self._retry = retry      # (dead_actor_id) -> (new ref, new origin)
        self._origin = origin    # replica actor id the ref dispatched to

    def result(self, timeout_s: Optional[float] = None):
        if self._ref is None:
            raise RuntimeError(
                "DeploymentResponse.result() is not available inside the "
                "event loop; use `await response` instead")
        import time as _time

        from ray_tpu.exceptions import ActorDiedError
        attempts = _DEATH_RETRIES if self._retry is not None else 0
        deadline = (None if timeout_s is None
                    else _time.monotonic() + timeout_s)
        while True:
            remaining = (None if deadline is None
                         else max(0.0, deadline - _time.monotonic()))
            try:
                return ray_tpu.get(self._ref, timeout=remaining)
            except ActorDiedError:
                if attempts <= 0:
                    raise
                attempts -= 1
                # Re-dispatch excluding the dead replica, and REPLACE the
                # stored ref: result() must stay idempotent (a second
                # call re-reads the successful attempt, never
                # re-executes the request).
                self._ref, self._origin = self._retry(self._origin)

    def __await__(self):
        if self._task is not None:
            return self._task.__await__()
        return self._ref.__await__()


class ServeStream:
    """Iterator over a streaming deployment response: yields the VALUES
    the remote generator produced (sync and async iteration), with the
    router's death handling folded in.

    A replica that dies BEFORE the first item was consumed is retried
    transparently on another replica (nothing observable was lost, same
    contract as the unary retry path).  A death MID-stream raises a
    typed :class:`~ray_tpu.exceptions.StreamBrokenError` carrying
    ``tokens_emitted`` — silently re-dispatching would replay the stream
    from index 0 and duplicate items the client already consumed.

    ``cancel()`` (or just abandoning the iterator) propagates a typed
    cancellation to the producing replica: the LLM serving path then
    retires the request mid-decode and its KV pages return to the
    pool."""

    def __init__(self, router, method: str, args: tuple, kwargs: dict,
                 model_id: Optional[str] = None, backpressure: int = 8,
                 timeout_s=None):
        self._router = router
        self._method = method
        self._args = args
        self._kwargs = kwargs
        self._model_id = model_id
        self._bp = backpressure
        self._timeout_s = timeout_s
        self._emitted = 0
        self._retries = _DEATH_RETRIES
        # Dispatch is LAZY (first iteration): the router's table refresh
        # blocks (ray_tpu.get, up to ~30s on an autoscaled-to-zero
        # deployment), so construction must stay cheap — async consumers
        # hop the dispatch through an executor in __anext__ instead of
        # stalling their event loop.
        self._gen = None
        self._origin = None

    def _start(self):
        self._gen, self._origin = \
            self._router.assign_streaming_with_origin(
                self._method, self._args, self._kwargs,
                model_id=self._model_id, backpressure=self._bp,
                timeout_s=self._timeout_s)

    def _on_death(self, e):
        from ray_tpu.exceptions import StreamBrokenError
        self._router.exclude(self._origin)
        if self._emitted == 0 and self._retries > 0:
            self._retries -= 1
            self._start()
            return
        raise StreamBrokenError(
            f"replica died after {self._emitted} streamed item(s)",
            tokens_emitted=self._emitted) from e

    def __iter__(self):
        return self

    def __next__(self):
        from ray_tpu.exceptions import ActorDiedError
        if self._gen is None:
            self._start()
        while True:
            try:
                ref = next(self._gen)
                val = ray_tpu.get(ref)
            except StopIteration:
                raise
            except ActorDiedError as e:
                self._on_death(e)
                continue
            self._emitted += 1
            return val

    def __aiter__(self):
        return self

    async def __anext__(self):
        import asyncio

        from ray_tpu.exceptions import ActorDiedError
        loop = asyncio.get_running_loop()
        if self._gen is None:
            # Dispatch (blocking router refresh) off-loop.
            await loop.run_in_executor(None, self._start)
        while True:
            try:
                ref = await self._gen.__anext__()
                val = await ref
            except StopAsyncIteration:
                raise
            except ActorDiedError as e:
                # The retry re-dispatch uses the sync router API
                # (blocking table refresh): hop off the event loop.
                await loop.run_in_executor(None, self._on_death, e)
                continue
            self._emitted += 1
            return val

    @property
    def tokens_emitted(self) -> int:
        return self._emitted

    def cancel(self) -> None:
        """Typed cancellation of the producing request (client
        disconnect): the replica's generator is closed and the engine
        frees the request's pages mid-decode.  No-op if never
        dispatched."""
        import ray_tpu as _rt
        if self._gen is None:
            return
        try:
            _rt.cancel(self._gen)
        except Exception:
            pass

    def completed(self):
        """Ref resolving when the remote generator finishes (dispatches
        the stream if iteration hasn't started; sync context only)."""
        if self._gen is None:
            self._start()
        return self._gen.completed()


class DeploymentHandle:
    """reference: serve/handle.py:692; method access via attribute chaining
    (handle.method.remote(...)), plain calls via handle.remote(...).
    .options(multiplexed_model_id=...) tags requests for model-affine
    routing (reference: handle.py options + multiplex);
    .options(stream=True) makes .remote() return a :class:`ServeStream`
    over the replica method's generator output (reference: handle
    streaming responses over Ray streaming generators)."""

    # Routers are shared per (deployment, process): handle copies and
    # .options() clones reuse one pushed routing table + inflight map.
    _routers: Dict[str, Router] = {}
    _routers_lock = threading.Lock()

    def __init__(self, deployment_name: str, method: str = "__call__",
                 multiplexed_model_id: Optional[str] = None,
                 stream: bool = False, stream_backpressure: int = 8,
                 timeout_s=None):
        self._deployment = deployment_name
        self._method = method
        self._model_id = multiplexed_model_id
        self._stream = stream
        self._stream_bp = stream_backpressure
        self._timeout_s = timeout_s

    def __getattr__(self, item):
        if item.startswith("_"):
            raise AttributeError(item)
        return DeploymentHandle(self._deployment, item, self._model_id,
                                self._stream, self._stream_bp,
                                self._timeout_s)

    def options(self, *, multiplexed_model_id: Optional[str] = None,
                method_name: Optional[str] = None,
                stream: Optional[bool] = None,
                stream_backpressure: Optional[int] = None,
                timeout_s=None) -> "DeploymentHandle":
        return DeploymentHandle(
            self._deployment, method_name or self._method,
            multiplexed_model_id
            if multiplexed_model_id is not None else self._model_id,
            self._stream if stream is None else stream,
            (self._stream_bp if stream_backpressure is None
             else stream_backpressure),
            self._timeout_s if timeout_s is None else timeout_s)

    def _get_router(self, controller=None) -> Router:
        # Locked check-then-act: concurrent first calls from several
        # driver threads must not build duplicate Routers (the loser's
        # pubsub subscription would leak and keep firing).
        with self._routers_lock:
            router = self._routers.get(self._deployment)
            if router is None:
                if controller is None:
                    controller = ray_tpu.get_actor(CONTROLLER_NAME)
                router = Router(controller, self._deployment)
                self._routers[self._deployment] = router
            return router

    def remote(self, *args, **kwargs):
        import asyncio
        if self._stream:
            # Streaming dispatch: returns a ServeStream (sync + async
            # iterable of values).  Router construction/dispatch use the
            # sync API — inside an event loop, hop through an executor
            # (the HTTP proxy does exactly that).
            return ServeStream(self._get_router(), self._method, args,
                               kwargs, model_id=self._model_id,
                               backpressure=self._stream_bp,
                               timeout_s=self._timeout_s)
        try:
            asyncio.get_running_loop()
        except RuntimeError:
            router = self._get_router()
            ref, origin = router.assign_with_origin(
                self._method, args, kwargs, model_id=self._model_id)

            def _retry(dead_origin):
                if dead_origin is not None:
                    router.exclude(dead_origin)
                return router.assign_with_origin(
                    self._method, args, kwargs, model_id=self._model_id)

            return DeploymentResponse(ref=ref, retry=_retry,
                                      origin=origin)
        # Called from inside the event loop (an async actor / another
        # deployment): dispatch eagerly on the loop, fully async.
        return DeploymentResponse(
            task=asyncio.ensure_future(self._remote_async(args, kwargs)))

    async def _remote_async(self, args, kwargs):
        router = self._routers.get(self._deployment)
        if router is None:
            from ray_tpu._private.worker import global_runtime
            from ray_tpu.actor import ActorHandle
            core = global_runtime().core
            info = await core.get_actor_info_async(name=CONTROLLER_NAME)
            if info is None:
                raise ValueError(f"no actor named {CONTROLLER_NAME!r}")
            controller = ActorHandle(bytes(info["actor_id"]),
                                     info.get("class_name", ""))
            router = self._get_router(controller)
        from ray_tpu.exceptions import ActorDiedError
        attempts = _DEATH_RETRIES
        while True:
            ref, origin = await router.assign_async_with_origin(
                self._method, args, kwargs, model_id=self._model_id)
            try:
                return await ref
            except ActorDiedError:
                if attempts <= 0:
                    raise
                attempts -= 1
                router.exclude(origin)

    def __reduce__(self):
        return (DeploymentHandle, (self._deployment, self._method,
                                   self._model_id, self._stream,
                                   self._stream_bp, self._timeout_s))


def _get_or_create_controller():
    try:
        return ray_tpu.get_actor(CONTROLLER_NAME)
    except ValueError:
        return ServeController.options(
            name=CONTROLLER_NAME, lifetime="detached",
            get_if_exists=True, max_restarts=1).remote()


def start(http_host: str = "127.0.0.1",
          http_port: Optional[int] = None,
          grpc_port: Optional[int] = None) -> Optional[int]:
    """Start the Serve control plane (reference: serve.start). HTTP/gRPC
    ingress only spin up when a port is given (0 = OS-assigned).  Returns
    the bound gRPC port when gRPC was requested."""
    global _proxy, _http_port, _grpc_proxy
    _get_or_create_controller()
    if http_port is not None and _proxy is None:
        from ._private.proxy import ProxyActor
        _proxy = ProxyActor.options(name="SERVE_PROXY",
                                    get_if_exists=True).remote(
            http_host, http_port)
        ray_tpu.get(_proxy.ready.remote(), timeout=60)
        _http_port = http_port
    if grpc_port is not None:
        if _grpc_proxy is None:
            from ._private.grpc_proxy import GrpcProxyActor
            _grpc_proxy = GrpcProxyActor.options(
                name="SERVE_GRPC_PROXY", get_if_exists=True).remote(
                http_host, grpc_port)
        # Idempotent: a repeated start(grpc_port=...) returns the port
        # the existing proxy is already bound to.
        return ray_tpu.get(_grpc_proxy.ready.remote(), timeout=60)
    return None


def run(app: Application, *, name: Optional[str] = None,
        route_prefix: Optional[str] = None,
        _blocking: bool = True) -> DeploymentHandle:
    """Deploy an application and return its handle (reference: serve.run).
    Waits for at least one replica to be live."""
    global _routes
    if not isinstance(app, Application):
        raise TypeError("serve.run expects Deployment.bind(...)")
    from ray_tpu._private.usage import record_library_usage
    record_library_usage("serve")
    controller = _get_or_create_controller()
    dep = app.deployment
    dep_name = name or dep.name
    opts = dep.ray_actor_options or {}
    tpus = opts.get("num_tpus") or (opts.get("resources") or {}).get("TPU")
    if tpus:
        from ray_tpu.tpu.accelerator import require_cluster_tpus
        require_cluster_tpus(tpus, f"a replica of deployment {dep_name!r}")
    blob = cloudpickle.dumps(dep._target)
    ray_tpu.get(controller.deploy.remote(
        dep_name, blob, app.init_args, app.init_kwargs,
        dep.num_replicas, dep.ray_actor_options,
        dep.autoscaling_config), timeout=120)
    _routes[route_prefix or dep.route_prefix] = dep_name
    if _proxy is not None:
        ray_tpu.get(_proxy.set_routes.remote(_routes), timeout=30)
    handle = DeploymentHandle(dep_name)
    if _blocking:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            table = ray_tpu.get(controller.get_routing_table.remote(
                dep_name, -1, 0.0), timeout=30)
            if table["replicas"]:
                return handle
            time.sleep(0.2)
        raise TimeoutError(f"deployment {dep_name} has no live replicas")
    return handle


def status() -> dict:
    """Cluster-wide Serve status (reference: serve.status() — per-app
    deployment status + replica states)."""
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    return ray_tpu.get(controller.get_status.remote(), timeout=60)


def delete(name: str) -> None:
    """Tear one deployment down (reference: serve.delete)."""
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    ray_tpu.get(controller.delete_deployment.remote(name), timeout=60)


def get_deployment_handle(deployment_name: str) -> DeploymentHandle:
    return DeploymentHandle(deployment_name)


def shutdown() -> None:
    """Tear down all deployments, the controller, and the proxies."""
    global _proxy, _grpc_proxy, _routes
    try:
        controller = ray_tpu.get_actor(CONTROLLER_NAME)
        ray_tpu.get(controller.graceful_shutdown.remote(), timeout=60)
        ray_tpu.kill(controller)
    except ValueError:
        pass
    for h in (_proxy, _grpc_proxy):
        if h is not None:
            try:
                ray_tpu.kill(h)
            except Exception:
                pass
    _proxy = _grpc_proxy = None
    for router in DeploymentHandle._routers.values():
        try:
            router.close()
        except Exception:
            pass
    DeploymentHandle._routers.clear()
    _routes = {}
