"""Semantic protein search over a prebuilt embedding index.

The analogue of the reference's ``examples/protein_search.py`` (FASTA
queries -> ESM encoder -> FAISS search): here queries embed through the
JAX ESM-2/ESM-C encoders and hit the exact MXU inner-product index
(``distllm_tpu.rag.search``). The index is built beforehand by the embed
pipeline, e.g.::

    python -m distllm_tpu.distributed_embedding \
        --config examples/embed/esm2.fasta.workstation.yaml

Then::

    python examples/protein_search.py \
        --dataset_dir /results/esm2_embeddings/merged \
        --encoder esm2 \
        --checkpoint /checkpoints/esm2_t33_650M_UR50D \
        --fasta queries.fasta --top_k 5
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

from distllm_tpu.utils import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--dataset_dir', type=Path, required=True,
                        help='Merged embedding dataset (build via embed + merge).')
    parser.add_argument('--fasta', type=Path, required=True,
                        help='FASTA file of query sequences.')
    parser.add_argument('--encoder', choices=['esm2', 'esmc', 'fake'],
                        default='esm2',
                        help="'fake' runs checkpoint-free (CI smoke).")
    parser.add_argument('--checkpoint', default=None,
                        help='Local encoder checkpoint directory '
                        '(required unless --encoder fake).')
    parser.add_argument('--top_k', type=int, default=5)
    parser.add_argument('--batch_size', type=int, default=8)
    parser.add_argument('--precision', choices=['float32', 'ubinary'],
                        default='float32')
    parser.add_argument('--output', type=Path, default=None,
                        help='Write JSONL results here (default: stdout).')
    parser.add_argument('--fake_embedding_size', type=int, default=16,
                        help='Embedding size for --encoder fake.')
    args = parser.parse_args()
    if args.encoder != 'fake' and not args.checkpoint:
        parser.error('--checkpoint is required unless --encoder fake')

    from distllm_tpu.embed.datasets.fasta import read_fasta
    from distllm_tpu.rag.search import RetrieverConfig

    retriever = RetrieverConfig(
        faiss_config={
            'name': 'tpu_index_v2',
            'dataset_dir': str(args.dataset_dir),
            'precision': args.precision,
        },
        encoder_config=(
            {'name': 'fake', 'embedding_size': args.fake_embedding_size}
            if args.encoder == 'fake'
            else {
                'name': args.encoder,
                'pretrained_model_name_or_path': args.checkpoint,
            }
        ),
        pooler_config={'name': 'mean'},
        batch_size=args.batch_size,
    ).get_retriever()

    sequences = read_fasta(args.fasta)
    queries = [seq.sequence for seq in sequences]
    results, _ = retriever.search(queries, top_k=args.top_k)

    out = args.output.open('w') if args.output else None
    for seq, scores, indices in zip(
        sequences, results.total_scores, results.total_indices
    ):
        hits = [
            {
                'score': float(score),
                'tag': tag,
            }
            for score, tag in zip(
                scores, retriever.get(list(indices), 'tags')
            )
        ]
        line = json.dumps({'query_tag': seq.tag, 'hits': hits})
        print(line, file=out or None)
    if out:
        out.close()
        print(f'wrote {len(queries)} query results to {args.output}')


if __name__ == '__main__':
    main()
