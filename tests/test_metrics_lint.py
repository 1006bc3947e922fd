"""scripts/metrics_lint.py — the committed cardinality budget.

Three contracts:

* the committed budget (conf/metrics_budget.json) is CONSISTENT with
  the live METRIC_TYPES registry (no stale families, every label
  bounded, products within budget);
* a REAL exposition — request + provenance + robustness + fleet
  families, exemplars included — lints clean against it;
* a smuggled label (new key on an existing family, or a family that
  never registered) FAILS, mechanically.
"""

import importlib.util
import os

import pytest

from omero_ms_image_region_tpu.utils import provenance, telemetry

SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts")


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(SCRIPTS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture(scope="module")
def lint():
    return _load_script("metrics_lint")


@pytest.fixture(scope="module")
def budget(lint):
    return lint.load_budget()


class TestRegistryBudget:
    def test_committed_budget_is_clean(self, lint, budget):
        assert lint.lint_registry(budget) == []

    def test_unbounded_label_fails(self, lint, budget):
        import copy
        bad = copy.deepcopy(budget)
        bad["families"]["imageregion_provenance_total"]["labels"] \
            .append("session")
        findings = lint.lint_registry(bad)
        assert any("session" in f for f in findings)

    def test_stale_family_fails(self, lint, budget):
        import copy
        bad = copy.deepcopy(budget)
        bad["families"]["imageregion_made_up_total"] = {"labels": []}
        findings = lint.lint_registry(bad)
        assert any("imageregion_made_up_total" in f for f in findings)

    def test_product_over_budget_fails(self, lint, budget):
        import copy
        bad = copy.deepcopy(budget)
        bad["families"]["imageregion_provenance_total"][
            "max_series"] = 2
        findings = lint.lint_registry(bad)
        assert any("label product" in f for f in findings)


class TestExpositionBudget:
    def _exposition(self) -> str:
        # Exercise the labeled families the budget is really about:
        # request histogram WITH an exemplar, provenance counters,
        # fleet + robustness labels.
        telemetry.REQUEST_HIST.observe(
            "render_image_region", 41.0,
            exemplar=("a1b2c3d4e5f60718", "render_cold"))
        telemetry.count_request("render_image_region", 200)
        telemetry.PROVENANCE.count(
            {"tier": "render_cold", "member": "m1", "stolen": 1})
        telemetry.PROVENANCE.count({"tier": "byte_cache"})
        telemetry.FLEET.count_routed("m0")
        telemetry.HOTKEY.count_promoted()
        telemetry.HOTKEY.count_balanced("m0")
        telemetry.PRESSURE.set_signal("hbm_frac", 0.5)
        telemetry.QOS.count_shed("bulk")
        telemetry.RESILIENCE.count_retry("image")
        return telemetry.finalize_exposition(
            telemetry.request_metric_lines(exemplars=True)
            + telemetry.robustness_metric_lines()
            + telemetry.fleet_metric_lines())

    def test_real_exposition_is_clean(self, lint, budget):
        assert lint.lint_exposition(self._exposition(), budget) == []

    def test_smuggled_label_key_fails(self, lint, budget):
        text = self._exposition() + (
            '\nimageregion_provenance_total{tier="peer",'
            'image="12345"} 1\n')
        findings = lint.lint_exposition(text, budget)
        assert any("image" in f and "provenance" in f
                   for f in findings)

    def test_unregistered_family_fails(self, lint, budget):
        text = self._exposition() + "\nimageregion_rogue_total 1\n"
        findings = lint.lint_exposition(text, budget)
        assert any("imageregion_rogue_total" in f for f in findings)

    def test_label_on_labelfree_family_fails(self, lint, budget):
        # A family the budget does NOT list gets labels=[] — any
        # label on it is the smuggle the check exists for.
        text = self._exposition() + (
            '\nimageregion_httpcache_304_total{member="m0"} 1\n')
        findings = lint.lint_exposition(text, budget)
        assert any("imageregion_httpcache_304_total" in f
                   for f in findings)

    def test_pixel_source_families_lint_clean_with_help_and_type(
            self, lint, budget):
        """PR 36's two label-free families, from a live
        ``PixelsService``: budgeted, typed, each with its own HELP."""
        from types import SimpleNamespace

        from omero_ms_image_region_tpu.io.service import PixelsService
        services = SimpleNamespace(pixels_service=PixelsService("/none"))
        text = telemetry.finalize_exposition([
            line for line in telemetry.device_metric_lines(services)
            if "_pixel_sources_" in line])
        for family, kind in (
                ("imageregion_pixel_sources_opened_total", "counter"),
                ("imageregion_pixel_sources_open", "gauge")):
            assert f"\n{family} 0\n" in text
            assert f"# TYPE {family} {kind}\n" in text
            assert text.count(f"# HELP {family} ") == 1
            assert telemetry.METRIC_HELP[family]
            assert budget["families"][family] == {"labels": []}
        assert lint.lint_exposition(text, budget) == []
        smuggled = text + 'imageregion_pixel_sources_open{image="7"} 1\n'
        assert any("imageregion_pixel_sources_open" in f
                   for f in lint.lint_exposition(smuggled, budget))

    def test_entropy_tiles_family_lints_clean(self, lint, budget):
        """PR 37's family: budgeted under ``path``, typed, one HELP."""
        family = "imageregion_entropy_tiles_total"
        text = telemetry.finalize_exposition([
            line for line in telemetry.device_metric_lines(None)
            if line.startswith(family)])
        for path in ("pooled", "inline"):
            assert f'\n{family}{{path="{path}"}} ' in text
        assert f"# TYPE {family} counter\n" in text
        assert text.count(f"# HELP {family} ") == 1
        assert telemetry.METRIC_HELP[family]
        assert budget["families"][family] == {"labels": ["path"]}
        assert lint.lint_exposition(text, budget) == []
        smuggled = text + f'{family}{{path="pooled",bucket="1024"}} 1\n'
        assert any(family in f
                   for f in lint.lint_exposition(smuggled, budget))

    def test_duplicate_loads_family_lints_clean(self, lint, budget):
        """The raw cache's lost load races: budgeted under ``by``,
        typed, one HELP, both series from the first scrape."""
        family = "imageregion_rawcache_duplicate_loads_total"
        telemetry.DUPLICATE_LOADS.count("prefetch")
        text = telemetry.finalize_exposition([
            line for line in telemetry.device_metric_lines(None)
            if line.startswith(family)])
        assert f'\n{family}{{by="prefetch"}} 1\n' in text
        assert f'\n{family}{{by="request"}} 0\n' in text
        assert f"# TYPE {family} counter\n" in text
        assert text.count(f"# HELP {family} ") == 1
        assert budget["families"][family] == {"labels": ["by"]}
        assert budget["label_bounds"]["by"] == len(
            telemetry.DUPLICATE_LOADS.BY)
        assert lint.lint_exposition(text, budget) == []
        smuggled = text + f'{family}{{by="prefetch",member="m0"}} 1\n'
        assert any(family in f
                   for f in lint.lint_exposition(smuggled, budget))

    def test_every_idle_class_fits_the_during_bound(self, budget):
        from omero_ms_image_region_tpu.utils import profile_summary as ps
        classes = set(ps.IDLE_ORDER) | {ps.NO_GROUP, ps.UNATTRIBUTED}
        assert len(classes) <= budget["label_bounds"]["during"]

    def test_exemplar_tail_tolerated(self, lint, budget):
        text = self._exposition()
        assert " # {" in text, "exemplar did not reach exposition"
        assert lint.lint_exposition(text, budget) == []

    def test_tier_vocabulary_is_closed(self):
        # A drifted tier string never reaches the label set.
        telemetry.PROVENANCE.count({"tier": "made-up-tier",
                                    "member": "m9"})
        lines = telemetry.PROVENANCE.metric_lines()
        assert any('tier="render_cold"' in ln for ln in lines)
        assert not any("made-up" in ln for ln in lines)
        for tier in provenance.TIERS:
            assert provenance.assemble(
                type("C", (), {"tile": None, "region": None,
                               "projection": None})(), 200
            )["tier"] in provenance.TIERS
