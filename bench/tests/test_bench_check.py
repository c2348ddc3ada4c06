"""The check that decides ``correct``, driven through a whole run at a tiny
size on the CPU (the look for a chip skipped): a sound run passes, the
float8 control put in the program's place fails at the committed limit, and
a token altered where the engine produces it turns ``correct`` false."""
import _paths  # noqa: F401
import pytest
from _tiny import run_tiny, tiny_cell

SEED = 2**31 + 21


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return run_tiny(tiny_cell("olmo1b.chat_tiers"), SEED,
                    tmp_path_factory.mktemp("sound"))


@pytest.fixture(scope="module")
def control(tmp_path_factory):
    return run_tiny(tiny_cell("olmo1b.chat_tiers"), SEED,
                    tmp_path_factory.mktemp("control"), control=True)


def test_sound_run_is_correct(sound):
    assert sound["correct"] is True
    assert sound["check"]["tiers_checked"]["value"] == 3
    assert sound["attempted"] > 0
    assert set(sound["metrics"]) == {"output_tok_s", "ttft_p50_ms",
                                     "itl_p95_ms", "setup_s"}
    assert list(sound)[-1] == "check"


def test_control_reads_wider_gap_than_the_program(control):
    program = control["check"]["program_gap"]["value"]
    gap = control["check"]["max_logit_gap"]["value"]
    assert gap > 0.0 and gap > 3 * program


def test_control_run_is_not_correct_at_the_committed_limit(control):
    from bench.run import load_json

    limit = load_json("workloads", "olmo1b.chat_tiers")["check"]["max_logit_gap"]
    check = control["check"]
    assert check["max_logit_gap"]["limit"] == limit
    assert check["max_logit_gap"]["value"] > limit
    assert check["program_gap"]["value"] <= limit
    assert check["tiers_checked"]["value"] == 3
    assert control["correct"] is False


def test_token_altered_where_produced_fails(tmp_path, monkeypatch):
    from repro.serving.engine import ServingEngine

    record = ServingEngine._record

    def altered(self, slot, req, tok, free, done):
        if len(req.out_tokens) == 1:          # the second token of each request
            tok = (tok + 1) % self.cfg.vocab_size
        return record(self, slot, req, tok, free, done)

    monkeypatch.setattr(ServingEngine, "_record", altered)
    line = run_tiny(tiny_cell("olmo1b.chat_tiers"), SEED, tmp_path)
    assert line["correct"] is False
    assert line["check"]["max_logit_gap"]["value"] > \
        line["check"]["max_logit_gap"]["limit"]
