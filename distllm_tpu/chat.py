"""Interactive RAG chat CLI.

Reference parity: ``distllm/chat.py`` and the argo-proxy variant
(``distllm/chat_argoproxy.py``): a REPL with conversation history, retrieval
on the LATEST user turn only (full history still goes into the prompt,
``chat.py:463-565``), a ``/inspect <query>`` command that prints retrieval
scores/attributes for debugging (``chat.py:362-424``), ``quit`` with
transcript save, and pluggable generator backends:

- ``http``  — OpenAI-compatible chat endpoint (the reference's vLLM server
  client, ``chat.py:124-171``); also covers Argo-proxy style endpoints
  (``chat_argoproxy.py:216-257``) via ``extra_body`` fields like ``user``.
- ``local`` — in-process paged-KV engine (no server needed).
- ``fake``  — deterministic echo for tests.

Config supports ``${env:VAR}`` substitution through BaseConfig (the
reference's ``chat_argoproxy.py:511-549`` feature).
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path
from typing import Any

from distllm_tpu.utils import BaseConfig


class ConversationPromptTemplate:
    """Render history + retrieved context into one prompt.

    Parity with the reference's conversation template (``chat.py:38-82``):
    the retrieval block is appended under a '[Context from retrieval]'
    header, then the full turn history, ending with 'assistant:'.
    """

    def __init__(self, system_prompt: str = '') -> None:
        self.system_prompt = system_prompt

    def render(
        self,
        history: list[dict[str, str]],
        contexts: list[str] | None = None,
        scores: list[float] | None = None,
    ) -> str:
        parts: list[str] = []
        if self.system_prompt:
            parts.append(self.system_prompt)
        if contexts:
            lines = [
                f'- (score {score:.3f}) {ctx}'
                for ctx, score in zip(contexts, scores or [0.0] * len(contexts))
            ]
            parts.append('[Context from retrieval]\n' + '\n'.join(lines))
        for turn in history:
            parts.append(f'{turn["role"]}: {turn["content"]}')
        parts.append('assistant:')
        return '\n\n'.join(parts)


def make_http_generator(
    base_url: str,
    model: str = 'default',
    api_key: str = '',
    temperature: float = 0.2,
    max_tokens: int = 1024,
    extra_body: dict[str, Any] | None = None,
    timeout: float = 300.0,
):
    """OpenAI-compatible HTTP backend — reuses :class:`ApiGenerator` (with
    its expo backoff) rather than maintaining a second client."""
    from distllm_tpu.generate.generators.api_backend import (
        ApiGenerator,
        ApiGeneratorConfig,
    )

    return ApiGenerator(
        ApiGeneratorConfig(
            provider='openai',  # an OpenAI-compatible server, whatever the
            # served model is named (e.g. a proxy hosting 'claude-*')
            openai_api_base=base_url,
            model=model,
            api_key=api_key,
            temperature=temperature,
            max_tokens=max_tokens,
            extra_body=extra_body or {},
            timeout=timeout,
        )
    )


class ChatAppConfig(BaseConfig):
    """YAML config for the chat apps (REPL + server)."""

    generator_config: dict[str, Any] = {'name': 'fake'}
    retriever_config: dict[str, Any] | None = None
    system_prompt: str = ''
    retrieval_top_k: int = 20
    retrieval_score_threshold: float = 0.1
    transcript_dir: Path | None = None

    def build_generator(self):
        backend = dict(self.generator_config)
        name = backend.pop('name', 'fake')
        if name == 'http':
            return make_http_generator(**backend)
        if name in ('tpu', 'vllm'):
            # Chat workloads are prefix-heavy by construction: the system
            # prompt and retrieved contexts lead every rendered prompt and
            # repeat across turns, so the engine's automatic prefix cache
            # (docs/prefix_caching.md) is on unless the config says
            # otherwise.
            backend.setdefault('enable_prefix_cache', True)
            # Server-side resilience defaults (docs/resilience.md): a
            # serving replica degrades per-request, never per-process —
            # a stuck request times out and frees its KV instead of
            # wedging a slot forever, and a failed window retries with
            # bounded backoff before quarantining only the affected
            # requests. Offline/batch callers building engines directly
            # keep the legacy propagate-first-exception contract.
            backend.setdefault('request_deadline_s', 120.0)
            backend.setdefault('max_dispatch_retries', 2)
        from distllm_tpu.generate import get_generator

        return get_generator({'name': name, **backend}, register=True)

    def build_retriever(self):
        if self.retriever_config is None:
            return None
        from distllm_tpu.rag.search import RetrieverConfig

        return RetrieverConfig(**self.retriever_config).get_retriever(
            register=True
        )


class ChatSession:
    """Drives one conversation; shared by the REPL and the server."""

    def __init__(self, config: ChatAppConfig) -> None:
        self.config = config
        self.generator = config.build_generator()
        self.retriever = config.build_retriever()
        self.template = ConversationPromptTemplate(config.system_prompt)
        self.history: list[dict[str, str]] = []

    def _retrieve(self, query: str) -> tuple[list[str], list[float]]:
        if self.retriever is None:
            return [], []
        results, _ = self.retriever.search(
            query,
            top_k=self.config.retrieval_top_k,
            score_threshold=self.config.retrieval_score_threshold,
        )
        indices = results.total_indices[0]
        contexts = self.retriever.get_texts(indices) if indices else []
        return contexts, results.total_scores[0]

    def ask(self, user_message: str) -> str:
        """One turn: retrieval on the latest message, history in prompt."""
        self.history.append({'role': 'user', 'content': user_message})
        contexts, scores = self._retrieve(user_message)
        prompt = self.template.render(self.history, contexts, scores)
        response = self.generator.generate([prompt])[0]
        self.history.append({'role': 'assistant', 'content': response})
        return response

    def inspect(self, query: str) -> list[dict[str, Any]]:
        """Retrieval-only debugging (``/inspect``; reference ``chat.py:362-424``)."""
        if self.retriever is None:
            return []
        results, _ = self.retriever.search(
            query, top_k=self.config.retrieval_top_k, score_threshold=-1e9
        )
        indices = results.total_indices[0]
        texts = self.retriever.get_texts(indices) if indices else []
        return [
            {'index': idx, 'score': score, 'text': text}
            for idx, score, text in zip(
                indices, results.total_scores[0], texts
            )
        ]

    def save_transcript(self) -> Path | None:
        if self.config.transcript_dir is None or not self.history:
            return None
        self.config.transcript_dir.mkdir(parents=True, exist_ok=True)
        path = (
            self.config.transcript_dir
            / f'chat_{time.strftime("%Y%m%d_%H%M%S")}.json'
        )
        path.write_text(json.dumps(self.history, indent=2))
        return path


def chat_with_model(config: ChatAppConfig, input_fn=input, echo=print) -> None:
    """The REPL (reference ``chat_with_model``, ``chat.py:463-565``)."""
    session = ChatSession(config)
    echo('Chat ready. Commands: quit | /inspect <query>')
    while True:
        try:
            user_message = input_fn('you> ').strip()
        except (EOFError, KeyboardInterrupt):
            user_message = 'quit'
        if not user_message:
            continue
        if user_message.lower() in ('quit', 'exit'):
            path = session.save_transcript()
            if path:
                echo(f'Transcript saved to {path}')
            echo('bye')
            return
        if user_message.startswith('/inspect '):
            for hit in session.inspect(user_message[len('/inspect ') :]):
                echo(f'[{hit["index"]}] score={hit["score"]:.4f} {hit["text"][:120]}')
            continue
        echo(f'assistant> {session.ask(user_message)}')


def main(argv: list[str] | None = None) -> int:
    from distllm_tpu.utils import enable_compile_cache

    enable_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--config', required=True, type=Path)
    args = parser.parse_args(argv)
    chat_with_model(ChatAppConfig.from_yaml(args.config))
    return 0


if __name__ == '__main__':
    raise SystemExit(main())
