"""The options of the token path (PR 29), of the Pallas flag kernels and of
the resident scan (PR 46) are gone, not deprecated: setting one fails as any
unknown option does, and nothing in the program reads one by name. This is
the only test file that spells the old names."""

import re
from pathlib import Path

import pytest

from spark_bam_tpu.core.config import Config

REPO = Path(__file__).resolve().parent.parent

#: What `grep` must not find outside this file (``ISSUE.md`` of PR 29, then
#: of PR 46).
GONE = (
    "device_inflate", "fused_count", "inflate_config", "InflateConfig",
    "tokenize_pack", "resolve_lz77", "count_window_tokens",
    "count_tokens_step", "count_window_raw", "lz77_resolve_pallas",
    "tokenize_pallas", "sbt_tokenize_deflate", "SPARK_BAM_LZ77",
    "SPARK_BAM_INFLATE",
    "flags_impl", "pallas_interpret", "pallas_kernels",
    "prefilter_check_flags", "full_check_flags", "interpret_for_platform",
    "resident_scan", "resident_chunk_bytes", "count_reads_resident",
    "count_scan", "make_count_scan", "SPARK_BAM_RESIDENT",
)


@pytest.mark.parametrize("option,value", [
    ("device_inflate", True), ("fused_count", True),
    ("inflate", "tokenize=device"),
    ("resident_scan", True), ("resident_chunk_bytes", 256 << 20),
])
def test_the_config_has_no_such_field(option, value):
    with pytest.raises(TypeError, match=option):
        Config(**{option: value})
    with pytest.raises(KeyError, match="Unknown config key"):
        Config.from_dict({f"spark.bam.{option.replace('_', '.')}": value})


@pytest.mark.parametrize("flag", [
    ["--inflate", "tokenize=device"], ["--resident"],
], ids=lambda flag: flag[0])
def test_the_cli_rejects_the_flag(flag, capsys):
    from spark_bam_tpu.cli.main import build_parser

    with pytest.raises(SystemExit) as exit_:
        build_parser().parse_args(["count-reads", *flag, "any.bam"])
    assert exit_.value.code == 2
    assert flag[0] in capsys.readouterr().err


@pytest.mark.parametrize("name", [
    "SPARK_BAM_INFLATE", "SPARK_BAM_LZ77",
    "SPARK_BAM_RESIDENT_SCAN", "SPARK_BAM_RESIDENT_CHUNK_BYTES",
])
def test_the_environment_variable_is_an_unknown_knob(name):
    """As ``SPARK_BAM_<anything else>`` is: ``Config.from_env`` refuses it,
    so a deployment that still sets one finds out at start-up."""
    def refusal(env):
        with pytest.raises(KeyError, match="Unknown config key") as e:
            Config.from_env(env)
        return type(e.value)

    assert refusal({name: "pallas"}) is refusal({"SPARK_BAM_NO_SUCH_KNOB": "1"})


def _built(backend, how):
    if how == "constructor":
        return Config(backend=backend)
    if how == "from_dict":
        return Config.from_dict({"spark.bam.backend": backend})
    return Config.from_env({"SPARK_BAM_BACKEND": backend})


@pytest.mark.parametrize("how", ["constructor", "from_dict", "from_env"])
@pytest.mark.parametrize("backend", Config.BACKENDS)
def test_the_backend_is_one_of_five(backend, how):
    assert len(Config.BACKENDS) == 5
    assert _built(backend, how).backend == backend


@pytest.mark.parametrize("how", ["constructor", "from_dict", "from_env"])
@pytest.mark.parametrize("backend", ["pallas", "xla", "TPU", ""])
def test_any_other_backend_is_refused_when_the_config_is_built(backend, how):
    """``pallas`` named an engine until PR 46. Left unchecked, a deployment
    that still sets it would run the NumPy engine unasked
    (``cli/app.device_engine`` answers False for a name it does not know);
    so it finds out at start-up, as with the removed options above."""
    expected = re.escape(" | ".join(Config.BACKENDS))
    with pytest.raises(ValueError, match=f"expected {expected}"):
        _built(backend, how)
    with pytest.raises(ValueError, match="Bad backend"):
        Config().replace(backend=backend)


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
