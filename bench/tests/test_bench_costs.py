"""bench/costs matches arithmetic done by hand at one shape per
configuration."""
import json
import os

from bench.harness.spec import load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
C = load_module(os.path.join(BENCH, "costs", "dense_transformer.py"), "c")


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_qwen15_4b_prefill_chunk():
    cfg = _cfg("qwen1.5-4b-tp1")
    # per layer 4 * 2560^2 (q, k, v, o; MHA) + 3 * 2560 * 6912 = 79,298,560
    # weights: 2 * 40 * 79,298,560 * 1024                = 6,496,138,035,200
    # attention: 4 * 40 * 20 * 128 * (1024 * 1025 / 2)   =   214,958,080,000
    # LM head at one position: 2 * 2560 * 151,936        =       777,912,320
    assert C.flops(cfg, [(0, 1024, 1)]) == 6_711_874_027_520
    # non-embedding parameters: 40 * (79,298,560 + 2 * 2560 norms
    # + 60 * 128 biases) + 2560 final norm = 3,172,456,960
    assert C.nonembed_params(cfg) == 3_172_456_960
    # bytes: (3,172,456,960 + 2560 * 151,936) * 2 = 7,122,826,240 weights
    # + 1024 * 2560 * 2 embedding rows + 1024 * 409,600 KV written
    assert C.kv_bytes_per_token(cfg) == 40 * 2 * 20 * 128 * 2 == 409_600
    assert C.bytes_moved(cfg, [(0, 1024, 1)]) == 7_547_499_520


def test_qwen3_14b_decode_row_per_chip():
    cfg = _cfg("qwen3-14b-tp4")
    # per layer 2 * 5120^2 + 2 * 5120 * 1024 + 3 * 5120 * 17408 = 330,301,440
    # weights: 2 * 40 * 330,301,440                    = 26,424,115,200
    # attention over 2000 keys: 4 * 40 * 40 * 128 * 2000 = 1,638,400,000
    # LM head: 2 * 5120 * 151,936                      =  1,555,824,640
    assert C.flops(cfg, [(1999, 1, 1)]) == 29_618_339_840
    # bytes: (40 * (330,301,440 + 2 * 5120 + 2 * 128) + 5120
    #         + 5120 * 151,936) * 2 = 27,980,789,760
    # + 5120 * 2 + 2000 * (40 * 2 * 8 * 128 * 2 = 163,840) = 28,308,480,000
    assert C.bytes_moved(cfg, [(1999, 1, 1)]) == 28_308_480_000
    assert C.per_chip(28_308_480_000, 4) == 7_077_120_000


def test_rows_add_up():
    cfg = _cfg("qwen3-14b-tp4")
    rows = [(0, 256, 1), (512, 256, 1)]
    assert C.flops(cfg, rows) == sum(C.flops(cfg, [r]) for r in rows)
