"""Batched serving example: prefill + decode across architecture families.

    PYTHONPATH=src python examples/serve_lm.py --arch gemma3-4b
    PYTHONPATH=src python examples/serve_lm.py --arch zamba2-2.7b   # SSM state path

Attention archs prefill the whole prompt in one pass and decode against the
ring-buffer KV cache; SSM/hybrid archs warm their recurrent state stepwise.
This is the same decode_step the decode_32k / long_500k dry-run cells lower
to 256/512 chips.
"""

import argparse

from repro.launch import serve
from repro.runtime.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-4b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    import sys
    sys.argv = ["serve", "--arch", args.arch, "--batch", str(args.batch),
                "--prompt-len", str(args.prompt_len), "--gen", str(args.gen)]
    serve.main()


if __name__ == "__main__":
    main()
