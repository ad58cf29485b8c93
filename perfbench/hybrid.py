#!/usr/bin/env python3
"""``blocks.py`` for a decoder with short-convolution layers: the same
traced run and ``blocks {...}`` line, its ``metrics`` holding the two
``ShortConv`` readers beside the five decoder readers (all seven are
``BENCHMARK.json`` entries since PR 37).

    python3 perfbench/hybrid.py --workload <cell> --seed <n> --seconds <s>
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import blocks  # noqa: E402

HYBRID_METRICS = ("short_conv_share", "short_conv_roofline")

if __name__ == "__main__":
    blocks.DECODER_METRICS = blocks.DECODER_METRICS + HYBRID_METRICS
    sys.exit(blocks.main())
