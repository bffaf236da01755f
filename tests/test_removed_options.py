"""The options of the token path (PR 29) are gone, not deprecated: setting
one fails as any unknown option does, and nothing in the program reads one
by name. This is the only test file that spells the old names."""

from pathlib import Path

import pytest

from spark_bam_tpu.core.config import Config

REPO = Path(__file__).resolve().parent.parent

#: What `grep` must not find outside this file (``ISSUE.md`` of PR 29).
GONE = (
    "device_inflate", "fused_count", "inflate_config", "InflateConfig",
    "tokenize_pack", "resolve_lz77", "count_window_tokens",
    "count_tokens_step", "count_window_raw", "lz77_resolve_pallas",
    "tokenize_pallas", "sbt_tokenize_deflate", "SPARK_BAM_LZ77",
    "SPARK_BAM_INFLATE",
)


@pytest.mark.parametrize("option,value", [
    ("device_inflate", True), ("fused_count", True),
    ("inflate", "tokenize=device"),
])
def test_the_config_has_no_such_field(option, value):
    with pytest.raises(TypeError, match=option):
        Config(**{option: value})
    with pytest.raises(KeyError, match="Unknown config key"):
        Config.from_dict({f"spark.bam.{option.replace('_', '.')}": value})


def test_the_cli_rejects_the_flag(capsys):
    from spark_bam_tpu.cli.main import build_parser

    with pytest.raises(SystemExit) as exit_:
        build_parser().parse_args(
            ["count-reads", "--inflate", "tokenize=device", "any.bam"])
    assert exit_.value.code == 2
    assert "--inflate" in capsys.readouterr().err


@pytest.mark.parametrize("name", ["SPARK_BAM_INFLATE", "SPARK_BAM_LZ77"])
def test_the_environment_variable_is_an_unknown_knob(name):
    """As ``SPARK_BAM_<anything else>`` is: ``Config.from_env`` refuses it,
    so a deployment that still sets one finds out at start-up."""
    def refusal(env):
        with pytest.raises(KeyError, match="Unknown config key") as e:
            Config.from_env(env)
        return type(e.value)

    assert refusal({name: "pallas"}) is refusal({"SPARK_BAM_NO_SUCH_KNOB": "1"})


@pytest.fixture(scope="module")
def sources():
    """Every file the acceptance ``grep`` reads, but this one."""
    roots = [REPO / "spark_bam_tpu", REPO / "tools", REPO / "tests",
             REPO / "docs"]
    files = [p for root in roots for p in root.rglob("*")
             if p.suffix in (".py", ".cpp", ".md", ".json")]
    files += [REPO / "chip_smoke.py", REPO / "README.md"]
    return {p: p.read_text(errors="replace") for p in files
            if p != Path(__file__).resolve()}


@pytest.mark.parametrize("name", GONE)
def test_nothing_spells_the_name(name, sources):
    assert len(sources) > 100
    found = [str(p.relative_to(REPO)) for p, text in sources.items()
             if name in text]
    assert not found, found
