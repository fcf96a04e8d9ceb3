"""Tests of what makes a decoder's block a file (bench/families/): run by
hand on the CPU, beside test_correct.py; ``pytest tests/`` does not collect
them:

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests -q

* a family that no file of the benchmark knows, with a configuration under
  another ``config.json``'s key names, runs a cell to ``correct`` true;
* nothing under bench/ but ``families/gpt2.py`` names a GPT-2 key, the
  program's configuration builder or its model module;
* an unknown or missing ``family`` fails with the list of the families;
* warm-up finds every prompt width between the shortest and the longest
  prompt's by asking, whatever the rule.
"""

from __future__ import annotations

import json
import os
import re
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", str(ROOT / ".pathway-cache" / "xla-rehearsal")
)
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT))

import rehearse  # noqa: E402
from pwbench import harness, spec  # noqa: E402

GPT2_KEYS = ("n_embd", "n_head", "n_layer", "n_inner", "n_positions")
OTHER_NAMES = {
    "n_embd": "hidden_size", "n_layer": "num_hidden_layers",
    "n_head": "num_attention_heads", "n_inner": "intermediate_size",
    "n_positions": "max_position_embeddings",
}

# hands on gpt2's functions, and reads the decoder's keys under the names
# of another published config.json
OTHER_FAMILY = '''
from pwbench import spec

_gpt2 = spec.family("gpt2")
globals().update({k: v for k, v in vars(_gpt2).items() if not k.startswith("_")})


def _keys(config):
    return {
        "vocab_size": config["vocab_size"], "n_embd": config["hidden_size"],
        "n_head": config["num_attention_heads"],
        "n_layer": config["num_hidden_layers"],
        "n_inner": config["intermediate_size"],
        "n_positions": config["max_position_embeddings"],
    }


def sizes(config):
    return {**_gpt2.sizes(_keys(config)), "family": "othernames"}


def program_config(config, dtype):
    return _gpt2.program_config(_keys(config), dtype)
'''


def test_a_family_no_file_knows_runs_a_cell(tmp_path, monkeypatch, capsys):
    (tmp_path / "othernames.py").write_text(OTHER_FAMILY)
    monkeypatch.setattr(spec, "FAMILY_DIRS", [BENCH / "families", tmp_path])
    tiny = json.loads((BENCH / "rehearsal" / "tiny.json").read_text())
    config = {OTHER_NAMES.get(k, k): v for k, v in tiny.items()}
    config["family"] = "othernames"
    assert not set(GPT2_KEYS) & set(config)
    (tmp_path / "tiny.json").write_text(json.dumps(config))
    (tmp_path / "tiny.limits.json").write_text(
        (BENCH / "rehearsal" / "tiny.limits.json").read_text()
    )
    preset = json.loads((BENCH / "rehearsal" / "BENCHMARK.json").read_text())
    preset["paths"] = [str(BENCH)]  # the mixes and the readers as they are
    preset["configs"][0]["file"] = str(tmp_path / "tiny.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(preset))
    code = rehearse.rehearse(
        "tiny.backlog", 13, 2.0, False, bench_file=tmp_path / "BENCHMARK.json"
    )
    counts = json.loads(capsys.readouterr().out)
    assert code == 0 and counts["correct"], counts["compared"]
    assert counts["attempted"] > 0 and counts["failed"] == 0
    assert counts["compared"]["logit_gap"]["tokens"] > 0


def test_only_the_gpt2_family_names_the_gpt2_block():
    named = re.compile(
        r"\b(?:" + "|".join(GPT2_KEYS + ("lm_config",)) + r")\b"
        r"|pathway_tpu\.models\.transformer"
        r"|from pathway_tpu\.models import[^\n]*\btransformer\b"
    )
    allowed = {BENCH / "families" / "gpt2.py", Path(__file__).resolve()}
    found = {
        str(path.relative_to(BENCH)): sorted(set(named.findall(path.read_text())))
        for path in sorted(BENCH.rglob("*.py")) if path not in allowed
    }
    assert not {k: v for k, v in found.items() if v}
    assert named.search((BENCH / "families" / "gpt2.py").read_text())


@pytest.mark.parametrize("config", [{"family": "gpt3"}, {}])
def test_an_unknown_family_fails_with_the_list(config):
    with pytest.raises(SystemExit) as e:
        spec.family_of(config)
    assert repr(config.get("family")) in str(e.value)
    assert "'gpt2'" in str(e.value)  # among the families, however many


@pytest.mark.parametrize("rule, lo, hi, widths, asked", [
    # the program's ladder at this cell's prompts: one width, one call
    (lambda n: -(-n // 256) * 256, 1234, 1250, [1280], [[1234, 1250]]),
    # powers of two with two widths between the ends
    (lambda n: 1 << (n - 1).bit_length(), 50, 400, [64, 128, 256, 512],
     [[50, 400], [65], [129], [257]]),
    # neighbouring widths: one prompt more, which runs at the longest's
    (lambda n: 1 << (n - 1).bit_length(), 50, 80, [64, 128], [[50, 80], [65]]),
])
def test_warm_up_asks_for_every_width(rule, lo, hi, widths, asked):
    ran: set[int] = set()
    calls = []

    def widths_of(lengths):
        calls.append(list(lengths))
        ran.update(rule(n) for n in lengths)
        return sorted(ran)

    assert harness._warm_prefill(widths_of, lo, hi) == widths
    assert calls == asked
