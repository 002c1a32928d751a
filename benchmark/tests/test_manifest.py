import copy
import os

import pytest

import manifest


@pytest.fixture(scope="module")
def m():
    return manifest.load_manifest()


def test_manifest_is_valid_and_every_named_file_exists(m):
    for w in m["workloads"]:
        entry, config = manifest.config_of(m, w)
        assert config["name"] == entry["name"]
        assert set(entry["reduced"]) <= set(config)
        traffic = manifest.traffic_of(w)
        assert traffic["name"] == w["traffic"]
        assert traffic["model"] in {e["name"] for e in config["repository"]}
        assert os.path.exists(os.path.join(
            manifest.HERE, "limits", w["name"] + ".json"))
        assert w["chips"] == 1
    for x in m["per_layer"]:
        spec, read = manifest.reader_of(x["name"])
        assert callable(read)
        assert (spec["name"], spec["unit"], spec["moves"], spec["layer"]) == (
            x["name"], x["unit"], x["moves"], x["layer"])


def test_step_mfu_stands_beside_every_kernel_roofline(m):
    mfus = {(x["moves"], c) for x in m["per_layer"] if "mfu" in x["name"]
            for c in x["workloads"]}
    for x in m["per_layer"]:
        if x["name"].endswith("_roofline"):
            assert all((x["moves"], c) in mfus for c in x["workloads"])


@pytest.mark.parametrize("breakage", [
    lambda m: m["workloads"][0].update(name="has space"),
    lambda m: m["end_to_end"][0].update(unit="tokens per second"),
    lambda m: m["end_to_end"][0].update(bound=0.2),
    lambda m: m["per_layer"][0].update(moves="no_such_metric"),
    lambda m: m["per_layer"][0].update(workloads=["no.such_cell"]),
    lambda m: m["workloads"].append(dict(m["workloads"][0], name="twin")),
    lambda m: m.update(run_seconds=52),
    lambda m: m["end_to_end"].pop(),            # setup_s gone
])
def test_broken_manifests_are_refused(m, breakage):
    broken = copy.deepcopy(m)
    breakage(broken)
    with pytest.raises(manifest.ManifestError):
        manifest.validate(broken)
