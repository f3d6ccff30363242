"""The bytes counted for one recorded call of each kernel, printed so
the arithmetic can be checked by hand (``pytest -s`` shows them).

Both calls are the first of their kernel in a traced window on one TPU
v5e (``fig9-lookup-typed``): a cascade call of 493 real queries over
three SSTable levels and no GLORAN level, and a compaction's merge of a
4,096-key run into a 20,480-key run.
"""

from __future__ import annotations

import importlib.util
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _module(name):
    spec = importlib.util.spec_from_file_location(
        f"kb_{name}", os.path.join(BENCH_DIR, "kernel_bytes", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CASCADE_CALL = {"kernel": "cascade", "n": 493,
                "key_cnt": [20480, 180224, 450560], "gl_cnt": [],
                "hashes": 6}
MERGE_CALL = {"kernel": "merge", "na": 4096, "nb": 20480}


def test_cascade_call_bytes():
    kb = _module("cascade")
    # Per query: 3 levels x 6 Bloom words x 4 B = 72; binary searches of
    # ceil(log2 K) = 15, 18 and 19 probes x 8 B = 416; inputs 16; masks
    # 12; positions 3 x 4 = 12.  528 B per query, x 493 queries.
    per_query = kb.bytes_per_query(CASCADE_CALL["key_cnt"], [], 6)
    total = kb.call_bytes(CASCADE_CALL)
    print(f"cascade: {per_query} B per query, {total} B for the call")
    assert per_query == 72 + 416 + 16 + 12 + 12 == 528
    assert total == 528 * 493 == 260_304
    # A GLORAN level of 1,000 areas adds ceil(log2 1000) = 10 probes of
    # 16 B per query.
    assert kb.bytes_per_query([20480], [1000], 6) - \
        kb.bytes_per_query([20480], [], 6) == 160


def test_merge_call_bytes():
    kb = _module("merge")
    # 4,096 keys x 15 probes (log2 20,480) + 20,480 keys x 12 probes
    # (log2 4,096), 4 B each; then 8 B per key for input and output.
    total = kb.call_bytes(MERGE_CALL)
    print(f"merge: {total} B for the call")
    assert total == 4 * (4096 * 15 + 20480 * 12) + 8 * 24576 == 1_425_408
