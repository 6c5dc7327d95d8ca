"""Batched serving example across architecture families.

Prefills a batch of prompts and decodes greedily for three different
architecture families — a KV-cache transformer (qwen2.5), the attention-free
RWKV6 (O(1) recurrent cache: the ``long_500k`` story), and the hybrid Hymba
(attention ∥ SSM) — through the same serve_prefill/serve_step interface the
dry-run lowers at production shapes.

  PYTHONPATH=src python examples/serve_lm.py
"""
from repro.configs import get
from repro.launch.serve import serve


def main():
    from repro.compile_cache import configure_caches

    configure_caches()
    for name in ("qwen2.5-14b", "rwkv6-1.6b", "hymba-1.5b"):
        cfg = get(name).reduced()
        tokens, stats = serve(cfg, batch=4, prompt_len=24, gen=12)
        print(f"{name:16s} generated {tokens.shape[1]} tokens/seq x "
              f"{tokens.shape[0]} seqs | prefill {stats['prefill_s']:.2f}s | "
              f"decode {stats['tok_per_s']:.1f} tok/s")


if __name__ == "__main__":
    main()
