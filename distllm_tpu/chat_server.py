"""OpenAI-compatible RAG chat server (aiohttp).

Reference parity: ``distllm/chat_server.py`` — ``POST /v1/chat/completions``
plus ``GET /health``; OpenAI messages are folded into the conversation
template; RAG runs in a worker thread (the event loop stays free); optional
single-delta SSE streaming; request extensions ``top_k`` and
``score_threshold``; config path from the ``DISTLLM_CHAT_CONFIG`` env var;
permissive CORS. FastAPI is unavailable in this environment, so the server
is aiohttp.

Serving note: prompts render as system prompt + retrieved contexts +
conversation — a shared, growing prefix across a session's turns — so the
in-process TPU engine runs with automatic prefix caching on by default
(``ChatAppConfig.build_generator``; knobs/metrics in
docs/prefix_caching.md, ``distllm_prefix_cache_*`` series at /metrics).

Observability surface (docs/observability.md):

- ``GET /metrics`` — Prometheus text exposition of the process registry
  (engine throughput, KV occupancy, queue depth, HTTP latency, request
  TTFT/TPOT/queue-wait, ...);
- ``GET /health`` — liveness plus uptime / in-flight / served counts;
- ``GET /loadinfo`` — cheap JSON load probe for the multi-replica router
  (queue depth, readiness, drain state, KV occupancy; docs/routing.md) —
  per-app/per-engine state, never a Prometheus text parse;
- ``GET /debug/traces?limit=N`` — most recent spans from the trace ring;
- ``GET /debug/flight?limit=N`` — most recent engine flight-recorder
  records (prefill/decode steps, request lifecycles, preemptions);
- ``GET /debug/perfetto?limit=N`` — the flight + span rings rendered as a
  Perfetto/``chrome://tracing`` trace-event JSON (open it at
  https://ui.perfetto.dev), request-id-correlated tracks included;
- ``GET /debug/history?limit=N&prefix=...`` — the metric-history ring
  (``observability/history.py``, ``distllm-history/v1`` schema): retained
  counter rates / gauge values / histogram quantile snapshots, sampled
  every ``DISTLLM_HISTORY_S`` seconds (default 1; 0 disables the
  sampler) by a background thread started with the app and stopped on
  cleanup;
- ``GET /debug/slo`` — the ``slo_status()`` ok/warn/page document
  (multi-window burn rates over ``distllm_request_slo_total``) plus the
  regression-sentinel state; arm the sentinel with
  ``DISTLLM_BASELINE=<envelope path>`` (the JSON of
  ``observability.baseline.build_envelope``) — a missing baseline is a
  counted disarm, never a startup failure;
- ``GET /debug/bundle`` — dump a full debug bundle (flight ring + metrics
  + traces + perfetto.json + startup.json + history.json + slo.json) to
  disk and return the written paths;
- ``GET /debug/xprof?seconds=N`` — bounded on-demand ``jax.profiler``
  capture to disk (one at a time; errors reported, never fatal).

Resilience surface (docs/resilience.md): an engine running SLO-aware
admission control sheds over-SLO requests as **429** with an honest
``Retry-After`` header; ``POST /drain?seconds=N`` stops admitting (new
completions get 503 + ``Retry-After``), waits for in-flight requests,
and flips ``GET /health`` to ``{"status": "draining", "ready": false}``
with a 503 status — the readiness signal a multi-replica router polls
(``distllm_server_ready`` is the scrape twin). Draining is one-way per
process: a drained replica restarts (the disk KV tier makes the restart
warm) rather than un-drains.

Request-scoped tracing: every ``POST /v1/chat/completions`` accepts an
``X-Request-Id`` header (one is generated when absent), binds it around
the whole retrieve/generate path (``observability.request_scope`` — spans
and the engine's request lifecycle records carry it), and echoes it back
both as the ``X-Request-Id`` response header and a ``request_id`` field in
the completion payload.

Multi-replica routing (docs/routing.md): every completion response
carries ``X-Distllm-Prefix-Digest`` + ``X-Distllm-Prefix-Depth`` — the
byte-level prefix digest chain the router's affinity maps learn replica
cache residency from (``router/affinity.py``; same chained hashing the
KV tiers key on).

Generation requests run under an optional stall watchdog
(``DISTLLM_WATCHDOG_S`` seconds, 0 = off): if the engine makes no
progress for that long mid-request, a debug bundle is dumped
automatically — the wedge explains itself even if the process is later
killed.

Run: ``DISTLLM_CHAT_CONFIG=cfg.yaml python -m distllm_tpu.chat_server --port 8000``
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import re
import time
import uuid

import distllm_tpu
from distllm_tpu.chat import ChatAppConfig, ChatSession
from distllm_tpu.resilience import EngineOverloaded
from distllm_tpu.router.affinity import (
    HEADER_DEPTH,
    HEADER_DIGEST,
    prompt_prefix_digests,
)
from distllm_tpu.observability import (
    HistorySampler,
    StallWatchdog,
    dump_debug_bundle,
    get_flight_recorder,
    get_metrics_history,
    get_profiler_capture,
    get_trace_buffer,
    install_regression_sentinel,
    install_slo_observer,
    instruments,
    render_prometheus,
    request_scope,
    slo_status,
    span,
    to_trace_events,
)

# Accepted inbound X-Request-Id shape; anything else (or nothing) gets a
# generated id — a client header must not be able to smuggle arbitrary
# bytes into trace attributes, flight records, and response headers.
_REQUEST_ID_RE = re.compile(r'^[A-Za-z0-9._:-]{1,128}$')


def _resolve_request_id(request) -> str:
    header = (request.headers.get('X-Request-Id') or '').strip()
    if _REQUEST_ID_RE.match(header):
        return header
    return f'req-{uuid.uuid4().hex[:16]}'


def _debug_dir(kind: str) -> str:
    """Where on-demand debug bundles land (``DISTLLM_DEBUG_DIR`` or
    ``./debug_bundles``), one timestamped directory per dump."""
    base = os.environ.get('DISTLLM_DEBUG_DIR') or os.path.join(
        os.getcwd(), 'debug_bundles'
    )
    stamp = time.strftime('%Y%m%d-%H%M%S')
    return os.path.join(base, f'{kind}_{stamp}_{os.getpid()}')


def _completion_payload(model: str, content: str, request_id: str) -> dict:
    return {
        'id': f'chatcmpl-{uuid.uuid4().hex[:24]}',
        'object': 'chat.completion',
        'created': int(time.time()),
        'model': model,
        'request_id': request_id,
        'choices': [
            {
                'index': 0,
                'message': {'role': 'assistant', 'content': content},
                'finish_reason': 'stop',
            }
        ],
        'usage': {
            'prompt_tokens': 0,
            'completion_tokens': 0,
            'total_tokens': 0,
        },
    }


def build_app(config: ChatAppConfig):
    from concurrent.futures import ThreadPoolExecutor

    from aiohttp import web

    session = ChatSession(config)
    template = session.template
    # Single-worker executor: the engine's scheduler/paged-KV state is NOT
    # thread-safe; concurrency comes from the engine's continuous batching,
    # not from parallel Python threads.
    executor = ThreadPoolExecutor(max_workers=1)
    started_at = time.time()

    # Known routes pre-register their latency/count series so the very
    # first /metrics scrape already carries the full schema.
    known_paths = (
        '/v1/chat/completions', '/health', '/metrics', '/drain', '/loadinfo',
    )
    for path in known_paths:
        instruments.HTTP_LATENCY.labels(path=path)

    # Continuous telemetry (docs/observability.md "Metric history"): one
    # background sampler folds the registry into the history ring every
    # DISTLLM_HISTORY_S seconds (default 1; 0/negative disables). The
    # SLO burn-rate observer and the regression sentinel ride the same
    # tick. The server owns the process sampler — engines only start
    # their own when EngineConfig.history_interval_s asks for one.
    instruments.SERVER_UPTIME.set(0.0)
    history = get_metrics_history()
    slo_observer = install_slo_observer(history)
    sentinel = install_regression_sentinel(
        history, baseline_path=os.environ.get('DISTLLM_BASELINE') or None
    )

    def _uptime_observer(h, now):
        instruments.SERVER_UPTIME.set(max(0.0, now - started_at))

    history.add_observer(_uptime_observer)
    history_interval_s = float(os.environ.get('DISTLLM_HISTORY_S', '1') or 0)
    sampler = (
        HistorySampler(history, interval_s=history_interval_s)
        if history_interval_s > 0
        else None
    )
    if sampler is not None:
        sampler.start()

    async def _stop_history(app) -> None:
        # on_cleanup: join the sampler thread (no leak after shutdown —
        # asserted by tests) and detach this app's observers so a later
        # build_app in the same process doesn't double-tick them.
        if sampler is not None:
            sampler.stop()
        history.remove_observer(_uptime_observer)
        history.remove_observer(slo_observer)
        sentinel.uninstall()

    # Drain lifecycle (docs/resilience.md): POST /drain flips this, new
    # completions get 503 + Retry-After while in-flight ones finish, and
    # /health turns not-ready (503) so a multi-replica router stops
    # sending traffic here. One-way per process by design — a drained
    # replica restarts rather than un-drains (restart is the recovery
    # unit the disk KV tier makes cheap). The SERVER_READY gauge is
    # process-wide and LATCHES that semantic: it starts at 1.0 (set at
    # instruments import) and only /drain ever writes it, so building a
    # second app in a process where an earlier app drained cannot
    # re-declare the process ready to the router — the conservative
    # reading for a scrape-driven route-away decision.
    # completions_in_flight counts ONLY /v1/chat/completions work (the
    # middleware's HTTP_IN_FLIGHT also counts the health/metrics polls a
    # draining server explicitly invites, which would keep /drain's wait
    # spuriously nonzero).
    state = {'draining': False, 'completions_in_flight': 0}

    def answer(messages, top_k, score_threshold, request_id):
        """Stateless per-request RAG (history comes from the client).

        Runs inside ``request_scope(request_id)`` (bound HERE, in the
        executor thread — ``run_in_executor`` does not carry the event
        loop's context over): the retrieve/generate spans and the
        engine's request lifecycle all pick up the propagated id.
        """
        with request_scope(request_id):
            return _answer_in_scope(messages, top_k, score_threshold)

    def _answer_in_scope(messages, top_k, score_threshold):
        latest = next(
            (m['content'] for m in reversed(messages) if m['role'] == 'user'),
            '',
        )
        contexts, scores = [], []
        if session.retriever is not None and latest:
            with span('chat-retrieve', top_k=top_k):
                results, _ = session.retriever.search(
                    latest, top_k=top_k, score_threshold=score_threshold
                )
                indices = results.total_indices[0]
                contexts = (
                    session.retriever.get_texts(indices) if indices else []
                )
                scores = results.total_scores[0]
        prompt = template.render(list(messages), contexts, scores)
        watchdog_s = float(os.environ.get('DISTLLM_WATCHDOG_S', '0') or 0)
        with span('chat-generate'):
            if watchdog_s <= 0:
                return session.generator.generate([prompt])[0]
            # Armed per request (an idle server is not a stall): if the
            # engine's flight ring stops advancing mid-generate, dump a
            # bundle so the wedge explains itself. Never kills the work.
            with StallWatchdog(
                watchdog_s,
                bundle_dir=_debug_dir('watchdog'),
                name='chat-generate',
            ):
                return session.generator.generate([prompt])[0]

    async def chat_completions(request: 'web.Request') -> 'web.StreamResponse':
        if state['draining']:
            # Drain lifecycle: stop admitting, finish in-flight. 503 (not
            # 429): the replica is going away, the client should try
            # another one, soon.
            instruments.RESILIENCE_SHED.labels(reason='draining').inc()
            get_flight_recorder().record('shed', reason='draining')
            return web.json_response(
                {'error': {'message': 'server is draining', 'type':
                           'draining'}},
                status=503,
                headers={'Retry-After': '5'},
            )
        body = await request.json()
        messages = body.get('messages', [])
        if not messages:
            return web.json_response(
                {'error': {'message': 'messages is required'}}, status=400
            )
        top_k = int(body.get('top_k', config.retrieval_top_k))
        score_threshold = float(
            body.get('score_threshold', config.retrieval_score_threshold)
        )
        model = body.get('model', 'distllm-tpu')
        request_id = _resolve_request_id(request)
        loop = asyncio.get_running_loop()
        state['completions_in_flight'] += 1
        try:
            content = await loop.run_in_executor(
                executor, answer, messages, top_k, score_threshold,
                request_id,
            )
        # distlint: disable=swallowed-exception -- the shed is fully surfaced: the engine already counted + flight-recorded it, and the 429 below lands in the HTTP middleware's status-class metric
        except EngineOverloaded as exc:
            # SLO-aware shedding (docs/resilience.md): the engine
            # predicted this request's TTFT would bust the SLO and
            # refused it at enqueue — surface the honest 429 the
            # prediction priced, instead of a response that arrives
            # after the client gave up.
            return web.json_response(
                {
                    'error': {
                        'message': str(exc),
                        'type': 'overloaded',
                        'predicted_ttft_s': round(
                            exc.predicted_ttft_s, 3
                        ),
                    },
                    'request_id': request_id,
                },
                status=429,
                headers={
                    'Retry-After': str(
                        max(1, math.ceil(exc.retry_after_s))
                    ),
                    'X-Request-Id': request_id,
                },
            )
        finally:
            state['completions_in_flight'] -= 1
        # Affinity-learning headers (docs/routing.md "Digest learning"):
        # having served this request, the replica now holds its whole
        # prompt prefix — advertise the deepest byte-chain digest + depth
        # so the router's per-replica map learns where the blocks live.
        # The router verifies the digest against its own chain before
        # trusting the sample, so the header can never poison routing.
        digest_headers = {'X-Request-Id': request_id}
        chain = prompt_prefix_digests(messages)
        if chain:
            digest_headers[HEADER_DIGEST] = chain[-1].hex()
            digest_headers[HEADER_DEPTH] = str(len(chain))
        if body.get('stream'):
            # Single-delta SSE streaming (reference ``chat_server.py:168-270``).
            response = web.StreamResponse(
                headers={
                    'Content-Type': 'text/event-stream',
                    'Cache-Control': 'no-cache',
                    **digest_headers,
                }
            )
            await response.prepare(request)
            chunk = {
                'id': f'chatcmpl-{uuid.uuid4().hex[:24]}',
                'object': 'chat.completion.chunk',
                'created': int(time.time()),
                'model': model,
                'request_id': request_id,
                'choices': [
                    {
                        'index': 0,
                        'delta': {'role': 'assistant', 'content': content},
                        'finish_reason': 'stop',
                    }
                ],
            }
            await response.write(
                f'data: {json.dumps(chunk)}\n\n'.encode()
            )
            await response.write(b'data: [DONE]\n\n')
            await response.write_eof()
            return response
        return web.json_response(
            _completion_payload(model, content, request_id),
            headers=digest_headers,
        )

    async def health(request: 'web.Request') -> 'web.Response':
        # In-flight includes this very request; report the others.
        in_flight = max(0, int(instruments.HTTP_IN_FLIGHT.value) - 1)
        draining = state['draining']
        instruments.SERVER_UPTIME.set(max(0.0, time.time() - started_at))
        # Readiness for the multi-replica router (ROADMAP item 2): the
        # body carries the flag AND the status code flips to 503 while
        # draining, so both field-readers and code-readers route away.
        return web.json_response(
            {
                'status': 'draining' if draining else 'ok',
                'ready': not draining,
                'draining': draining,
                'version': distllm_tpu.__version__,
                'uptime_s': round(time.time() - started_at, 3),
                'in_flight': in_flight,
                'requests_served': int(instruments.HTTP_RESPONSES.value),
            },
            status=503 if draining else 200,
        )

    async def drain(request: 'web.Request') -> 'web.Response':
        """POST /drain: stop admitting, finish in-flight
        (docs/resilience.md "Drain lifecycle"). Flips /health to
        not-ready immediately, then waits (bounded by ``?seconds=N``,
        default 30) for in-flight completions to finish; ``drained`` in
        the response says whether the wait emptied the server."""
        try:
            wait_s = float(request.query.get('seconds', '30'))
        # distlint: disable=swallowed-exception -- input validation surfaced to the client as a 400 and counted by the HTTP middleware's status-class metric
        except ValueError:
            return web.json_response(
                {'error': {'message': 'seconds must be a number'}},
                status=400,
            )
        if not math.isfinite(wait_s):
            return web.json_response(
                {'error': {'message': 'seconds must be finite'}},
                status=400,
            )
        wait_s = min(max(wait_s, 0.0), 300.0)
        state['draining'] = True
        instruments.SERVER_READY.set(0.0)
        get_flight_recorder().record('event', event='drain_started')
        deadline = time.monotonic() + wait_s

        def completions_in_flight() -> int:
            # ONLY completion work counts: the middleware's in-flight
            # gauge also sees the /health polls and /metrics scrapes a
            # draining server invites, which would report drained:false
            # with zero real work running.
            return max(0, int(state['completions_in_flight']))

        while completions_in_flight() > 0 and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        remaining = completions_in_flight()
        get_flight_recorder().record(
            'event', event='drain_finished', in_flight_remaining=remaining,
        )
        return web.json_response(
            {
                'draining': True,
                'drained': remaining == 0,
                'in_flight_remaining': remaining,
            }
        )

    async def loadinfo(request: 'web.Request') -> 'web.Response':
        """``GET /loadinfo`` — the router's hot-path load probe
        (docs/routing.md "Least-loaded fallback"): queue depth,
        readiness, drain state, and KV occupancy as a tiny JSON doc, so
        the router never parses Prometheus text per routing decision.
        ``/metrics`` stays unchanged for scrapes. Reads THIS app's drain
        flag and THIS engine's scheduler — unlike the process-wide
        gauges, correct even with several in-process replicas (the
        topology of ``tests/test_router.py``). Always 200: a draining replica still answers, the
        body says to route away."""
        engine = getattr(session.generator, 'engine', None)
        sched = getattr(engine, 'sched', None)
        queue_depth = running = 0
        kv_occupancy = 0.0
        if sched is not None:
            queue_depth = int(sched.num_waiting)
            running = int(sched.num_running)
            usable = max(1, int(engine.config.num_blocks) - 1)
            in_use = max(0, usable - int(sched.num_free_blocks))
            kv_occupancy = round(in_use / usable, 4)
        draining = state['draining']
        return web.json_response(
            {
                'ready': not draining,
                'draining': draining,
                'queue_depth': queue_depth,
                'running': running,
                'in_flight': int(state['completions_in_flight']),
                'kv_occupancy': kv_occupancy,
            }
        )

    async def metrics(request: 'web.Request') -> 'web.Response':
        return web.Response(
            body=render_prometheus().encode('utf-8'),
            headers={
                'Content-Type': 'text/plain; version=0.0.4; charset=utf-8'
            },
        )

    async def traces(request: 'web.Request') -> 'web.Response':
        try:
            limit = int(request.query.get('limit', '100'))
        # distlint: disable=swallowed-exception -- input validation surfaced to the client as a 400 and counted by the HTTP middleware's status-class metric
        except ValueError:
            return web.json_response(
                {'error': {'message': 'limit must be an integer'}}, status=400
            )
        spans = get_trace_buffer().snapshot(limit=max(1, limit))
        return web.json_response(
            {'spans': [s.to_dict() for s in spans if s.end_ns is not None]}
        )

    async def flight(request: 'web.Request') -> 'web.Response':
        try:
            limit = int(request.query.get('limit', '200'))
        # distlint: disable=swallowed-exception -- input validation surfaced to the client as a 400 and counted by the HTTP middleware's status-class metric
        except ValueError:
            return web.json_response(
                {'error': {'message': 'limit must be an integer'}}, status=400
            )
        recorder = get_flight_recorder()
        return web.json_response(
            {
                'records': recorder.snapshot(limit=max(1, limit)),
                'total_recorded': recorder.total_recorded,
                'capacity': recorder.capacity,
            }
        )

    async def perfetto(request: 'web.Request') -> 'web.Response':
        try:
            limit = int(request.query.get('limit', '2000'))
        # distlint: disable=swallowed-exception -- input validation surfaced to the client as a 400 and counted by the HTTP middleware's status-class metric
        except ValueError:
            return web.json_response(
                {'error': {'message': 'limit must be an integer'}}, status=400
            )
        limit = max(1, limit)

        def build() -> str:
            # Rendering + sorting thousands of events is real CPU work;
            # like bundle(), keep it off the event loop (default pool,
            # not the single-worker engine executor).
            doc = to_trace_events(
                get_flight_recorder().snapshot(limit=limit),
                [
                    s.to_dict()
                    for s in get_trace_buffer().snapshot(limit=limit)
                    if s.end_ns is not None
                ],
                history=history.snapshot(limit=limit),
            )
            return json.dumps(doc)

        loop = asyncio.get_running_loop()
        body = await loop.run_in_executor(None, build)
        return web.Response(
            body=body.encode('utf-8'),
            headers={'Content-Type': 'application/json'},
        )

    async def history_endpoint(request: 'web.Request') -> 'web.Response':
        """``GET /debug/history?limit=N&prefix=...`` — the retained
        metric history (``distllm-history/v1`` schema; limit trims each
        series to its newest N points, default 120)."""
        try:
            limit = int(request.query.get('limit', '120'))
        # distlint: disable=swallowed-exception -- input validation surfaced to the client as a 400 and counted by the HTTP middleware's status-class metric
        except ValueError:
            return web.json_response(
                {'error': {'message': 'limit must be an integer'}}, status=400
            )
        prefix = request.query.get('prefix') or None
        doc = history.snapshot(limit=max(1, limit), prefix=prefix)
        doc['sampler_running'] = bool(sampler is not None and sampler.running)
        return web.json_response(doc)

    async def slo_endpoint(request: 'web.Request') -> 'web.Response':
        """``GET /debug/slo`` — burn-rate verdict + sentinel state (the
        per-replica signal feed for the multi-replica router)."""
        instruments.SERVER_UPTIME.set(max(0.0, time.time() - started_at))
        return web.json_response(
            {**slo_status(history), 'sentinel': sentinel.status()}
        )

    async def bundle(request: 'web.Request') -> 'web.Response':
        directory = _debug_dir('bundle')
        # Default thread pool, NOT the single-worker engine executor: the
        # dump (disk writes + possible device-memory capture) must neither
        # freeze the event loop nor queue behind a wedged generate — a
        # wedge is exactly when this endpoint gets called.
        loop = asyncio.get_running_loop()
        paths = await loop.run_in_executor(
            None,
            lambda: dump_debug_bundle(directory, reason='GET /debug/bundle'),
        )
        return web.json_response({'bundle_dir': directory, 'paths': paths})

    async def xprof(request: 'web.Request') -> 'web.Response':
        """On-demand bounded profiler capture (observability/profiling.py):
        ``GET /debug/xprof?seconds=N`` blocks for N seconds of
        ``jax.profiler`` capture and returns the trace directory (XPlane +
        TensorBoard format). One capture at a time — a concurrent request
        gets 409; an unsupported backend gets 501, never a dead server."""
        try:
            seconds = float(request.query.get('seconds', '2'))
        # distlint: disable=swallowed-exception -- the NaN sentinel routes to the 400 response two lines down; the client-surfaced status is the signal
        except ValueError:
            seconds = math.nan
        # NaN passes float() and slides through min/max clamps unchanged.
        if not math.isfinite(seconds):
            return web.json_response(
                {'error': {'message': 'seconds must be a finite number'}},
                status=400,
            )
        seconds = min(max(seconds, 0.1), 60.0)
        directory = _debug_dir('xprof')
        capture = get_profiler_capture()
        # Default thread pool (like bundle/perfetto): the capture sleep
        # must not freeze the event loop or queue behind a wedged
        # generate — a wedge is exactly when an operator wants a profile.
        loop = asyncio.get_running_loop()
        result = await loop.run_in_executor(
            None, lambda: capture.capture(directory, seconds)
        )
        status = (
            200 if result['ok'] else 409 if result['rejected'] else 501
        )
        return web.json_response(
            {**result, 'seconds': seconds, 'state': capture.state()},
            status=status,
        )

    async def preflight(request: 'web.Request') -> 'web.Response':
        return web.Response(status=204)

    @web.middleware
    async def cors(request, handler):
        path = request.path if request.path in known_paths else 'other'
        instruments.HTTP_IN_FLIGHT.inc()
        start = time.perf_counter()
        status = 500
        try:
            response = await handler(request)
            status = response.status
        except web.HTTPException as exc:
            status = exc.status
            raise
        finally:
            instruments.HTTP_IN_FLIGHT.dec()
            instruments.HTTP_LATENCY.labels(path=path).observe(
                time.perf_counter() - start
            )
            instruments.HTTP_REQUESTS.labels(
                path=path, status=f'{status // 100}xx'
            ).inc()
            instruments.HTTP_RESPONSES.inc()
        response.headers['Access-Control-Allow-Origin'] = '*'
        response.headers['Access-Control-Allow-Headers'] = '*'
        response.headers['Access-Control-Allow-Methods'] = 'GET, POST, OPTIONS'
        return response

    app = web.Application(middlewares=[cors])
    app.router.add_post('/v1/chat/completions', chat_completions)
    app.router.add_get('/health', health)
    app.router.add_post('/drain', drain)
    app.router.add_get('/metrics', metrics)
    app.router.add_get('/loadinfo', loadinfo)
    app.router.add_get('/debug/traces', traces)
    app.router.add_get('/debug/flight', flight)
    app.router.add_get('/debug/perfetto', perfetto)
    app.router.add_get('/debug/history', history_endpoint)
    app.router.add_get('/debug/slo', slo_endpoint)
    app.router.add_get('/debug/bundle', bundle)
    app.router.add_get('/debug/xprof', xprof)
    # Browser preflight for any path (CORS headers added by the middleware).
    app.router.add_route('OPTIONS', '/{tail:.*}', preflight)
    app.on_cleanup.append(_stop_history)
    return app


def main(argv: list[str] | None = None) -> int:
    from distllm_tpu.utils import enable_compile_cache

    enable_compile_cache()
    from aiohttp import web

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--config', type=str, default=None)
    parser.add_argument('--host', default='0.0.0.0')
    parser.add_argument('--port', type=int, default=8000)
    args = parser.parse_args(argv)

    # Attribute the REAL backend init here, before the session/engine
    # build touches the device through weight loading — a wedged PJRT
    # client init is otherwise invisible (the r03/r04 failure mode).
    from distllm_tpu.observability import record_backend_init

    record_backend_init()

    config_path = args.config or os.environ.get('DISTLLM_CHAT_CONFIG')
    config = (
        ChatAppConfig.from_yaml(config_path) if config_path else ChatAppConfig()
    )
    web.run_app(build_app(config), host=args.host, port=args.port)
    return 0


if __name__ == '__main__':
    raise SystemExit(main())
