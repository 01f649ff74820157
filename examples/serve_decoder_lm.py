"""Serving example: export a decoder LM, then serve it with the
continuous-batching runtime (paged KV cache + ragged paged attention).

The export is the "converted decoder" form — the naive
matmul/softmax/matmul attention composition an exported user model
carries; the engine's pass pipeline rewrites it onto the fused
attention op at load, and the paged decode path never pads a
mixed-length batch to max-seq.

Run: python examples/serve_decoder_lm.py [--tiny]
(--tiny shrinks the model/load for the CI smoke; flow is identical.)
"""
import argparse
import os
import shutil
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.inference.gpt2_decoder import (  # noqa: E402
    DecoderConfig, export_decoder)
from paddle_tpu.inference.serving import (  # noqa: E402
    Request, ServingEngine)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    hidden, layers, n_req = (32, 2, 6) if args.tiny else (128, 4, 24)

    cfg = DecoderConfig(vocab_size=256, hidden=hidden, num_heads=4,
                        num_layers=layers, max_seq_len=256)
    export_dir = tempfile.mkdtemp()
    export_decoder(export_dir, cfg, seed=0)

    eng = ServingEngine(model_dir=export_dir, num_pages=64, page_size=8,
                        max_batch=4, token_budget=128,
                        prefill_bucket_min=8)
    rng = np.random.RandomState(0)
    reqs = [Request(i, rng.randint(0, 256, size=int(n)).tolist(),
                    max_new_tokens=8)
            for i, n in enumerate(rng.randint(3, 24, size=n_req))]
    for r in reqs:
        eng.submit(r)
    steps = 0
    while eng.has_work():
        for ev in eng.step():
            if ev.finished:
                print(f"step {steps}: request {ev.req_id} finished "
                      f"({len(reqs[ev.req_id].out_tokens)} tokens)")
        steps += 1

    # spot-check one request against one-at-a-time reference decoding
    oracle = eng.core.greedy_reference(reqs[0].prompt, 8)
    assert reqs[0].out_tokens == oracle, (reqs[0].out_tokens, oracle)
    print(f"served {len(reqs)} requests in {steps} steps; "
          f"kv peak {eng.kv.stats()['peak_pages']} pages, "
          f"scheduler {eng.stats}; request 0 matches reference: OK")
    shutil.rmtree(export_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
