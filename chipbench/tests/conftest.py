"""Tests of the benchmark itself, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

They are outside the repository's test suite (``testpaths`` is ``tests``).
"""

import copy
import json
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

import pytest  # noqa: E402

from chipbench import cells  # noqa: E402

# each configuration's widths, cut to a size the CPU runs in seconds;
# every other key is the configuration file's own
SMALL = {
    "granite-3-2b": {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
                     "num_key_value_heads": 2, "head_dim": 16, "attention_multiplier": 0.25,
                     "intermediate_size": 128,
                     "vocab_size": 500, "padded_vocab_size": 2048},
    "mamba2-2.7b": {"n_layer": 2, "d_model": 64, "headdim": 16, "d_state": 16,
                    "chunk_size": 8, "vocab_size": 500, "padded_vocab_size": 2048},
}
SMALL_TRAFFIC = {"slots": 3, "max_seq": 40, "prompt_buckets": [8, 16],
                 "output_buckets": [4, 8], "check_requests": 3}
# logits of these small models spread about a tenth as far as the full
# size's, so their limit is scaled with them: sound bfloat16 runs read a
# few thousandths here, far under it
SMALL_LIMITS = {"logit_gap_max": 0.05}


def small_cell(workload: str, **traffic) -> cells.Cell:
    """``workload`` as BENCHMARK.json has it, at a small size."""
    cell = copy.deepcopy(cells.load_cell(workload))
    cell.config.update(SMALL[cell.config["name"]])
    cell.traffic = dict(SMALL_TRAFFIC, **traffic)
    cell.limits = dict(SMALL_LIMITS)
    return cell


@pytest.fixture(params=["granite-3-2b.decode_heavy", "mamba2-2.7b.prefill_heavy"])
def cell(request):
    return small_cell(request.param)


def load_json(path):
    with open(path) as f:
        return json.load(f)
