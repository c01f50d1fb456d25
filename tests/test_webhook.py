"""Mutating webhook behavior (reference webhook_test.go)."""

import base64
import json

from vtpu.device.quota import QuotaManager
from vtpu.scheduler.webhook import WebHook
from vtpu.util import types as t

from tests.helpers import register_tpu_backend, tpu_pod


def _review(pod):
    return {"apiVersion": "admission.k8s.io/v1", "kind": "AdmissionReview",
            "request": {"uid": "r1", "object": pod}}


def _patch_ops(resp):
    return json.loads(base64.b64decode(resp["response"]["patch"]))


def test_webhook_mutates_device_pod():
    register_tpu_backend()
    wh = WebHook()
    out = wh.handle(_review(tpu_pod("p", tpumem=4096)))
    assert out["response"]["allowed"]
    ops = _patch_ops(out)
    scheduler_op = [o for o in ops if o["path"] == "/spec/schedulerName"][0]
    assert scheduler_op["value"] == t.SCHEDULER_NAME
    containers = [o for o in ops if o["path"] == "/spec/containers"][0]["value"]
    assert containers[0]["resources"]["limits"]["google.com/tpu"] == "1"


def test_webhook_ignores_plain_pod():
    register_tpu_backend()
    out = WebHook().handle(_review({"spec": {"containers": [{"name": "c"}]}}))
    assert out["response"]["allowed"]
    assert "patch" not in out["response"]


def test_webhook_skips_privileged_and_foreign():
    register_tpu_backend()
    pod = tpu_pod("p", tpumem=4096)
    pod["spec"]["containers"][0]["securityContext"] = {"privileged": True}
    out = WebHook().handle(_review(pod))
    assert "patch" not in out["response"]

    pod = tpu_pod("p", tpumem=4096)
    pod["spec"]["schedulerName"] = "volcano"
    out = WebHook().handle(_review(pod))
    assert "patch" not in out["response"]


def test_webhook_denies_preset_nodename():
    register_tpu_backend()
    pod = tpu_pod("p", tpumem=4096)
    pod["spec"]["nodeName"] = "some-node"
    out = WebHook().handle(_review(pod))
    assert out["response"]["allowed"] is False


def test_webhook_quota_precheck():
    qm = QuotaManager()
    register_tpu_backend(quota=qm)
    qm.add_quota({"metadata": {"name": "q", "namespace": "team"},
                  "spec": {"hard": {"limits.google.com/tpumem": 2048}}})
    wh = WebHook(qm)
    out = wh.handle(_review(tpu_pod("p", tpumem=4096, ns="team")))
    assert out["response"]["allowed"] is False
    out = wh.handle(_review(tpu_pod("p", tpumem=2048, ns="team")))
    assert out["response"]["allowed"] is True


def test_webhook_mutates_init_container_and_patches_spec():
    """A device ask in an init container must be normalized at
    admission like an app container's (the reference webhook walks only
    spec.containers — that hole is closed here), and the JSONPatch must
    carry the mutated initContainers back."""
    register_tpu_backend()
    wh = WebHook()
    pod = tpu_pod("p", init_limits={"google.com/tpumem": "4096"})
    out = wh.handle(_review(pod))
    assert out["response"]["allowed"]
    ops = _patch_ops(out)
    init_ops = [o for o in ops if o["path"] == "/spec/initContainers"]
    assert len(init_ops) == 1
    init_ctr = init_ops[0]["value"][0]
    assert init_ctr["resources"]["limits"]["google.com/tpu"] == "1"


def test_webhook_quota_precheck_counts_init_containers():
    qm = QuotaManager()
    register_tpu_backend(quota=qm)
    qm.add_quota({"metadata": {"name": "q", "namespace": "team"},
                  "spec": {"hard": {"limits.google.com/tpumem": 2048}}})
    wh = WebHook(qm)
    out = wh.handle(_review(
        tpu_pod("p", ns="team", init_limits={"google.com/tpumem": "4096"})))
    assert out["response"]["allowed"] is False
    out = wh.handle(_review(
        tpu_pod("p", ns="team", init_limits={"google.com/tpumem": "2048"})))
    assert out["response"]["allowed"] is True
