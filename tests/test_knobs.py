"""Knob registry <-> source <-> docs consistency (tier-1).

Three guards that keep docs/knobs.md from silently drifting:
1. every ``REPRO_*`` env name read anywhere under src/ is registered in
   ``core.knobs.KNOBS`` (the scanner canonicalises per-hop f-strings and
   the faults.py ``_env_*`` helper dispatch);
2. docs/knobs.md is byte-identical to what the registry renders
   (``scripts/gen_knobs.py --check`` runs the same comparison in CI);
3. README links every docs page and all intra-repo markdown links in
   README/docs resolve.
"""
import importlib.util
import re
from pathlib import Path

from repro.core.knobs import (KNOBS, registry_names, render_markdown,
                              scan_env_reads)

REPO = Path(__file__).resolve().parents[1]


def test_every_env_read_is_registered():
    scanned = scan_env_reads(REPO / "src")
    missing = scanned - registry_names()
    assert not missing, (
        f"REPRO_* env reads missing from core/knobs.py KNOBS: "
        f"{sorted(missing)} -- register them and regenerate "
        f"docs/knobs.md")


def test_no_dead_registry_entries():
    """Every registered knob is actually read somewhere -- entries must
    be pruned when the code stops reading them."""
    scanned = scan_env_reads(REPO / "src")
    dead = registry_names() - scanned
    assert not dead, (
        f"registered knobs no longer read anywhere under src/: "
        f"{sorted(dead)}")


def test_scanner_sees_known_knobs():
    """The scanner itself works: spot-check one of each read idiom --
    direct literal, module constant, constant+suffix composition,
    per-hop f-string, and the _env_* helper dispatch."""
    scanned = scan_env_reads(REPO / "src")
    assert "REPRO_CHAIN_MICROBATCH" in scanned      # direct literal
    assert "REPRO_CONV_SEARCH" in scanned           # SEARCH_ENV constant
    assert "REPRO_LINK_RETRIES" in scanned          # ENV_PREFIX + "RETRIES"
    assert "REPRO_LINK{k}_WIRE_DTYPE" in scanned    # per-hop f-string
    assert "REPRO_LINK{k}_DROP" in scanned          # _env_float("DROP", ...)
    assert "REPRO_TIER_CRASH" in scanned            # _tier_env_float(...)
    assert "REPRO_TIER{k}_CRASH_WINDOWS" in scanned  # per-tier wrapper
    assert "REPRO_LINK_BACKOFF_FACTOR" in scanned   # RetryPolicy.from_env


def test_knobs_md_up_to_date():
    path = REPO / "docs" / "knobs.md"
    assert path.exists(), "docs/knobs.md missing: run scripts/gen_knobs.py"
    assert path.read_text() == render_markdown(), (
        "docs/knobs.md is stale: regenerate with "
        "`PYTHONPATH=src python scripts/gen_knobs.py`")


def test_registry_rows_well_formed():
    names = [k.name for k in KNOBS]
    assert len(names) == len(set(names)), "duplicate knob names"
    for k in KNOBS:
        assert k.name.startswith("REPRO_")
        assert k.description and k.resolved_in
        if k.per_hop:
            assert "{k}" in k.per_hop


def test_readme_links_all_docs_pages():
    readme = (REPO / "README.md").read_text()
    for page in ("docs/architecture.md", "docs/runtime.md",
                 "docs/serving.md", "docs/knobs.md"):
        assert page in readme, f"README does not link {page}"
        assert (REPO / page).exists()


def test_intra_repo_markdown_links_resolve():
    """Same check the CI docs job runs via scripts/check_links.py."""
    spec = importlib.util.spec_from_file_location(
        "check_links", REPO / "scripts" / "check_links.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    broken = mod.check(mod.md_files())
    assert not broken, "\n".join(broken)


def test_compile_cache_placed_from_outside(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code;
    without it the cache is the fixed, gitignored ``<checkout>/.jax_cache``
    (a per-process or temporary path would never be hit again)."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(REPO / ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_docs_reference_real_modules():
    """Module paths cited in the hand-written docs exist (cheap rot
    guard for the architecture pages)."""
    pat = re.compile(r"`((?:core|runtime|serving|kernels|models|launch)/"
                     r"[a-z_0-9]+\.py)`")
    for page in ("architecture.md", "runtime.md", "serving.md"):
        text = (REPO / "docs" / page).read_text()
        for mod_path in pat.findall(text):
            assert (REPO / "src" / "repro" / mod_path).exists(), (
                f"docs/{page} cites missing module {mod_path}")
