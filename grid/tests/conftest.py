"""The grid's own tests run on the CPU at toy widths, in seconds, and
describe no TPU topology at import: four virtual CPU devices stand in for
the four-chip host, and the compile cache is a throw-away directory."""

import json
import os
import shutil
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="grid_tests_cache_"))

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

TOY_SERVE_MODEL = dict(vocab_size=97, n_layer=2, n_embd=32, n_head=4,
                       n_positions=64, dtype="float32")
TOY_TRAIN_MODEL = dict(vocab_size=50, n_layer=1, d_model=16, d_inner=32,
                       n_head=2, dropout=0.1, label_smoothing=0.1)


def _rewrite(path, change):
    with open(path) as f:
        doc = json.load(f)
    change(doc)
    with open(path, "w") as f:
        json.dump(doc, f)


@pytest.fixture
def toy_root(tmp_path):
    """A copy of BENCHMARK.json and grid/'s data files in which both
    configurations have toy widths and the traffic toy lengths: the
    rehearsal that walks every path of the drivers before chip time is
    spent. A fixture of the tests, not an option of the command."""
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for sub in ("cells", "configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(ROOT, "grid", sub),
                        os.path.join(root, "grid", sub))

    def serve(doc):
        doc["model"] = dict(TOY_SERVE_MODEL)
        doc["engine"] = dict(slots=4, page_size=8, max_seq=64, max_queue=64)

    def train(doc):
        doc["model"] = dict(TOY_TRAIN_MODEL)
        doc["trainer"].update(rows_per_chip=2, seq=8)

    def mix(doc):
        doc.update(prompt_len={"dist": "log_uniform", "lo": 4, "hi": 24},
                   output_len={"dist": "uniform", "lo": 17, "hi": 30},
                   prompt_buckets=[8, 16, 24], preroll_s=0.3, max_total=60)
        doc["arrivals"]["rate_per_s"] = 25.0

    cfg = os.path.join(root, "grid", "configs")
    _rewrite(os.path.join(cfg, "gpt2-small-serve.json"), serve)
    _rewrite(os.path.join(cfg, "transformer-base-train.json"), train)
    for name in ("chat-sat", "doc-steady"):
        _rewrite(os.path.join(root, "grid", "traffic", name + ".json"), mix)
    return root
