"""Prefix-affinity HTTP router for N chat_server replicas.

An asyncio (aiohttp) front-end that load-balances the OpenAI-compatible
surface (``POST /v1/chat/completions``) across replicas, routing each
request to the replica most likely to already hold its KV blocks
(docs/routing.md). Entry point: ``scripts/router.py``.

Policies (``RouterConfig.policy``):

- ``prefix_affinity`` (default) — score every healthy replica by the
  longest prefix of the request's byte-level digest chain present in its
  learned :class:`~distllm_tpu.router.affinity.AffinityMap`; deepest
  match wins (``decision=affinity``), depth 0 everywhere falls back to
  least-loaded.
- ``least_loaded`` — lightest ``GET /loadinfo`` queue (queue_depth, then
  in-flight, then KV occupancy), probed with a short-TTL cache so one
  routing decision never burns a round trip on a warm entry.
- ``round_robin`` — the baseline rotation (a control arm).

Health integration: a background probe loop polls each replica's
``/health``; connection failure or a non-ready answer removes it from
rotation. ``dead`` replicas rejoin when probes recover; ``draining``
(POST /drain observed) is ONE-WAY — a drained replica never rejoins and
its affinity map is forgotten (its process will restart with a new cache;
the disk tier makes that restart warm, but residency must be re-learned).
An in-flight request whose replica dies mid-proxy (or races a drain) is
retried ONCE on a healthy peer with an honest ``X-Distllm-Router-Retry``
marker; a replica's 429 + Retry-After admission rejection propagates to
the client untouched — backpressure is the replica's call, and retrying
it elsewhere would defeat admission control. Every proxied response also
carries ``X-Distllm-Router-Replica`` naming the serving replica.

The router keeps no per-request state beyond the bounded affinity maps;
it is itself stateless across restarts (maps re-learn from headers).
"""

from __future__ import annotations

import asyncio
import time
from typing import Literal

from distllm_tpu.observability import instruments, render_prometheus
from distllm_tpu.router.affinity import (
    DEFAULT_BLOCK_BYTES,
    HEADER_DEPTH,
    HEADER_DIGEST,
    HEADER_REPLICA,
    HEADER_RETRY,
    AffinityMap,
    prompt_prefix_digests,
)
from distllm_tpu.utils import BaseConfig

# Response headers relayed verbatim from replica to client (plus the
# router's own markers). Hop-by-hop headers stay out.
_RELAY_HEADERS = (
    'Content-Type',
    'Retry-After',
    'X-Request-Id',
    HEADER_DIGEST,
    HEADER_DEPTH,
)


class RouterConfig(BaseConfig):
    """Knobs for the multi-replica router (docs/routing.md knob table)."""

    # Replica base URLs ('http://host:port'), the initial rotation.
    replicas: tuple[str, ...] = ()
    policy: Literal[
        'prefix_affinity', 'least_loaded', 'round_robin'
    ] = 'prefix_affinity'
    # Digest-chain granularity in prompt-prefix BYTES; must match what
    # the replicas hash into their response headers (both sides default
    # to affinity.DEFAULT_BLOCK_BYTES).
    affinity_block_bytes: int = DEFAULT_BLOCK_BYTES
    # Bound of each per-replica digest LRU map.
    affinity_map_size: int = 4096
    # /loadinfo probe cache TTL: one routing decision on a warm entry
    # costs zero round trips.
    loadinfo_ttl_s: float = 0.25
    # Background /health probe period.
    health_interval_s: float = 2.0
    # Upstream completion timeout per proxy attempt.
    request_timeout_s: float = 300.0


class Replica:
    """Rotation state for one replica (mutated only on the router loop)."""

    def __init__(self, url: str) -> None:
        self.url = url.rstrip('/')
        # Short display name for headers/traces: 'host:port'.
        self.name = self.url.split('//', 1)[-1]
        self.state = 'healthy'  # healthy | dead | draining
        self.load: dict | None = None
        self.load_at = 0.0

    @property
    def in_rotation(self) -> bool:
        return self.state == 'healthy'

    def mark_dead(self) -> None:
        # Drain outranks dead: a draining replica that stops answering
        # is still drained — it must not rejoin when probes recover.
        if self.state != 'draining':
            self.state = 'dead'

    def mark_draining(self) -> None:
        self.state = 'draining'

    def mark_healthy(self) -> None:
        # One-way drain: only dead recovers.
        if self.state == 'dead':
            self.state = 'healthy'


def build_router_app(config: RouterConfig):
    from aiohttp import ClientSession, ClientTimeout, web
    import aiohttp

    replicas = [Replica(url) for url in config.replicas]
    affinity = AffinityMap(config.affinity_map_size)
    state = {'rr_index': 0, 'client': None, 'health_task': None}

    def client() -> 'ClientSession':
        # Created lazily on the router loop (ClientSession binds to it).
        if state['client'] is None:
            state['client'] = ClientSession(
                timeout=ClientTimeout(total=config.request_timeout_s)
            )
        return state['client']

    def _publish_states() -> None:
        for label in ('healthy', 'draining', 'dead'):
            instruments.ROUTER_REPLICAS.labels(state=label).set(
                sum(1 for r in replicas if r.state == label)
            )
        instruments.ROUTER_AFFINITY_ENTRIES.set(affinity.entries())

    _publish_states()

    async def _probe(replica: Replica) -> None:
        try:
            async with client().get(
                f'{replica.url}/health',
                timeout=ClientTimeout(total=max(1.0, config.health_interval_s)),
            ) as resp:
                doc = await resp.json()
        # distlint: disable=swallowed-exception -- an unreachable replica IS the probe's answer: it leaves rotation (state=dead, ROUTER_REPLICAS gauge) and rejoins when probes recover
        except Exception:
            replica.mark_dead()
            return
        if doc.get('draining'):
            if replica.state != 'draining':
                replica.mark_draining()
                # Its cache dies with the process; re-learning on a
                # restart is cheaper than routing warm traffic to a
                # replica that will refuse it.
                affinity.drop(replica.name)
        elif doc.get('ready'):
            replica.mark_healthy()
        else:
            replica.mark_dead()

    async def _health_loop() -> None:
        while True:
            await asyncio.gather(*(_probe(r) for r in replicas))
            _publish_states()
            await asyncio.sleep(config.health_interval_s)

    async def _loadinfo(replica: Replica) -> dict | None:
        now = time.monotonic()
        if replica.load is not None and (
            now - replica.load_at < config.loadinfo_ttl_s
        ):
            return replica.load
        try:
            async with client().get(
                f'{replica.url}/loadinfo',
                timeout=ClientTimeout(total=max(1.0, config.loadinfo_ttl_s * 4)),
            ) as resp:
                replica.load = await resp.json()
                replica.load_at = now
                return replica.load
        # distlint: disable=swallowed-exception -- a failed load probe demotes the replica to dead (gauge + rotation state), and the pick falls through to the remaining candidates
        except Exception:
            replica.mark_dead()
            return None

    async def _pick_least_loaded(
        candidates: list[Replica],
    ) -> Replica | None:
        loads = await asyncio.gather(*(_loadinfo(r) for r in candidates))
        best: tuple | None = None
        best_replica: Replica | None = None
        for replica, load in zip(candidates, loads):
            if load is None or not replica.in_rotation:
                continue
            key = (
                int(load.get('queue_depth', 0)),
                int(load.get('in_flight', 0)),
                float(load.get('kv_occupancy', 0.0)),
            )
            if best is None or key < best:
                best, best_replica = key, replica
        return best_replica

    def _pick_round_robin(candidates: list[Replica]) -> Replica:
        pick = candidates[state['rr_index'] % len(candidates)]
        state['rr_index'] += 1
        return pick

    async def _pick(
        chain: list[bytes], exclude: Replica | None = None
    ) -> tuple[Replica | None, str]:
        """One routing decision: (replica, decision-label)."""
        candidates = [
            r for r in replicas if r.in_rotation and r is not exclude
        ]
        if not candidates:
            return None, 'least_loaded'
        if config.policy == 'round_robin':
            return _pick_round_robin(candidates), 'round_robin'
        if config.policy == 'prefix_affinity' and chain:
            scored = [
                (affinity.score(r.name, chain), i, r)
                for i, r in enumerate(candidates)
            ]
            depth, _, best = max(scored)
            if depth > 0:
                return best, 'affinity'
        picked = await _pick_least_loaded(candidates)
        if picked is None and candidates:
            # Every load probe failed this instant but candidates were
            # in rotation — rotate rather than refuse.
            alive = [r for r in candidates if r.in_rotation]
            if alive:
                return _pick_round_robin(alive), 'round_robin'
        return picked, 'least_loaded'

    async def _proxy_once(
        replica: Replica, body: bytes, headers: dict
    ) -> tuple[int, dict, bytes]:
        async with client().post(
            f'{replica.url}/v1/chat/completions',
            data=body,
            headers=headers,
        ) as resp:
            payload = await resp.read()
            return resp.status, dict(resp.headers), payload

    async def chat_completions(request: 'web.Request') -> 'web.Response':
        t_start = time.perf_counter()
        body = await request.read()
        try:
            import json as _json

            messages = _json.loads(body or b'{}').get('messages', [])
        # distlint: disable=swallowed-exception -- an unparseable body is the replica's 400 to issue, not the router's: routing degrades to least-loaded and the request is proxied as-is
        except ValueError:
            messages = []
        chain = (
            prompt_prefix_digests(messages, config.affinity_block_bytes)
            if isinstance(messages, list)
            else []
        )
        fwd_headers = {'Content-Type': 'application/json'}
        inbound_rid = request.headers.get('X-Request-Id')
        if inbound_rid:
            fwd_headers['X-Request-Id'] = inbound_rid

        retried = False
        attempt_exclude: Replica | None = None
        for attempt in range(2):
            replica, decision = await _pick(chain, exclude=attempt_exclude)
            if replica is None:
                break
            try:
                status, up_headers, payload = await _proxy_once(
                    replica, body, fwd_headers
                )
            except (aiohttp.ClientError, asyncio.TimeoutError):
                # The failover contract: the dead replica leaves
                # rotation, the request retries ONCE on a healthy peer
                # (ROUTER_RETRIES counts it), and exhaustion lands in
                # distllm_router_failures_total below.
                replica.mark_dead()
                _publish_states()
                attempt_exclude = replica
                if attempt == 0:
                    retried = True
                    instruments.ROUTER_RETRIES.inc()
                continue
            if status == 503 and replica.state != 'draining':
                # The replica refused because it is going away (drain
                # races the health poll). Nothing was processed — safe
                # to move the request, with the honest retry marker.
                replica.mark_draining()
                affinity.drop(replica.name)
                _publish_states()
                attempt_exclude = replica
                if attempt == 0:
                    retried = True
                    instruments.ROUTER_RETRIES.inc()
                    continue
            instruments.ROUTER_REQUESTS.labels(decision=decision).inc()
            if status == 429:
                # Admission control spoke: propagate untouched (body,
                # Retry-After and all) — never retried elsewhere.
                instruments.ROUTER_UPSTREAM_REJECTIONS.inc()
            else:
                learned = affinity.verify_and_learn(
                    replica.name,
                    chain,
                    up_headers.get(HEADER_DIGEST),
                    up_headers.get(HEADER_DEPTH),
                )
                if learned:
                    instruments.ROUTER_AFFINITY_ENTRIES.set(
                        affinity.entries()
                    )
            out_headers = {
                k: up_headers[k] for k in _RELAY_HEADERS if k in up_headers
            }
            out_headers[HEADER_REPLICA] = replica.name
            if retried:
                out_headers[HEADER_RETRY] = '1'
            instruments.ROUTER_PROXY_SECONDS.observe(
                time.perf_counter() - t_start
            )
            return web.Response(
                status=status, body=payload, headers=out_headers
            )
        instruments.ROUTER_FAILURES.inc()
        instruments.ROUTER_PROXY_SECONDS.observe(
            time.perf_counter() - t_start
        )
        return web.json_response(
            {
                'error': {
                    'message': 'no replica available',
                    'type': 'router_unavailable',
                }
            },
            status=503,
            headers={'Retry-After': '5'},
        )

    async def health(request: 'web.Request') -> 'web.Response':
        healthy = sum(1 for r in replicas if r.in_rotation)
        return web.json_response(
            {
                'status': 'ok' if healthy else 'unavailable',
                'ready': healthy > 0,
                'policy': config.policy,
                'replicas': {r.name: r.state for r in replicas},
                'affinity_entries': affinity.entries(),
            },
            status=200 if healthy else 503,
        )

    async def metrics(request: 'web.Request') -> 'web.Response':
        return web.Response(
            body=render_prometheus().encode('utf-8'),
            headers={
                'Content-Type': 'text/plain; version=0.0.4; charset=utf-8'
            },
        )

    async def _start(app) -> None:
        state['health_task'] = asyncio.create_task(_health_loop())

    async def _stop(app) -> None:
        task = state['health_task']
        if task is not None:
            task.cancel()
            try:
                await task
            # distlint: disable=swallowed-exception -- the cancellation IS the intended outcome of shutdown; nothing degraded
            except asyncio.CancelledError:
                pass
        if state['client'] is not None:
            await state['client'].close()

    app = web.Application()
    app.router.add_post('/v1/chat/completions', chat_completions)
    app.router.add_get('/health', health)
    app.router.add_get('/metrics', metrics)
    app.on_startup.append(_start)
    app.on_cleanup.append(_stop)
    # Exposed for tests: drive rotation state directly.
    app['router_replicas'] = replicas
    app['router_affinity'] = affinity
    app['router_config'] = config
    return app
