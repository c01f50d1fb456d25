"""Helm chart sanity (no helm binary in CI): values/Chart schemas parse, every
template has balanced delimiters, and the values keys the templates reference
actually exist (the classic chart-rot failure)."""

import re
from pathlib import Path

import yaml

CHART = Path(__file__).resolve().parent.parent / "charts" / "vtpu"


def _values():
    return yaml.safe_load((CHART / "values.yaml").read_text())


def test_chart_and_values_parse():
    chart = yaml.safe_load((CHART / "Chart.yaml").read_text())
    assert chart["name"] == "vtpu"
    values = _values()
    assert values["scheduler"]["schedulerName"] == "vtpu-scheduler"
    assert values["deviceConfig"]["tpu"]["resourceCountName"] == "google.com/tpu"


def test_templates_balanced_delimiters():
    for tpl in CHART.glob("templates/**/*"):
        if not tpl.is_file():
            continue
        text = tpl.read_text()
        assert text.count("{{") == text.count("}}"), f"unbalanced delimiters in {tpl}"
        opens = len(re.findall(r"\{\{-? *(?:if|range|with|define)\b", text))
        closes = len(re.findall(r"\{\{-? *end\b", text))
        assert opens == closes, f"{tpl}: {opens} blocks vs {closes} ends"


def test_template_value_paths_exist():
    values = _values()
    pattern = re.compile(r"\.Values\.([A-Za-z0-9_.]+)")
    for tpl in CHART.glob("templates/**/*.yaml"):
        for ref in pattern.findall(tpl.read_text()):
            node = values
            for part in ref.split("."):
                assert isinstance(node, dict) and part in node, (
                    f"{tpl.name}: .Values.{ref} missing from values.yaml"
                )
                node = node[part]


def test_certgen_flow_without_cert_manager():
    """With certManager disabled the chart must self-
    provision webhook TLS — a create job (secret) + patch job (caBundle),
    gated on the certgen toggle and mutually exclusive with cert-manager."""
    values = _values()
    webhook = values["scheduler"]["webhook"]
    assert webhook["certgen"]["enabled"] is True
    assert not webhook["certManager"]["enabled"]
    text = (CHART / "templates" / "scheduler" / "certgen.yaml").read_text()
    assert "certgen-create" in text and "certgen-patch" in text
    assert '"helm.sh/hook": pre-install,pre-upgrade' in text
    assert '"helm.sh/hook": post-install,post-upgrade' in text
    assert "not .Values.scheduler.webhook.certManager.enabled" in text
    assert "--secret-name={{ .Values.scheduler.webhook.tlsSecretName }}" in text
    # the patch job targets the webhook configuration this chart creates
    wh = (CHART / "templates" / "scheduler" / "webhook.yaml").read_text()
    assert '-webhook' in wh
    assert "--webhook-name={{ include \"vtpu.scheduler.fullname\" . }}-webhook" in text
