"""The CPU rehearsal's benchmark, built from the committed one: the
committed ``BENCHMARK.json``, configurations and traffic mixes with the
names and sizes of ``rehearsal/overrides.json`` put in, and the
rehearsal's own server postures (``rehearsal/configs/*.yaml``).  Nothing
of it is a copy kept in step by hand."""

import json
import os
import shutil

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH_DIR)


def build_rehearsal(root: str) -> str:
    with open(os.path.join(HERE, "rehearsal", "overrides.json")) as f:
        over = json.load(f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        text = f.read()
    # Configuration names are part of the cells' names and of the
    # metrics' ``workloads`` lists: renamed everywhere at once.
    for real, sets in over["configs"].items():
        text = text.replace(real, sets["name"])
    bench = json.loads(text)
    os.makedirs(os.path.join(root, "configs"))
    os.makedirs(os.path.join(root, "traffic"))
    tiny_of = {sets["name"]: (real, sets)
               for real, sets in over["configs"].items()}
    for entry in bench["configs"]:
        real, sets = tiny_of[entry["name"]]
        with open(os.path.join(BENCH_DIR, "configs", real + ".json")) as f:
            config = json.load(f)
        config.update(sets)
        entry["file"] = f"configs/{entry['name']}.json"
        with open(os.path.join(root, entry["file"]), "w") as f:
            json.dump(config, f, indent=1)
        shutil.copy(os.path.join(HERE, "rehearsal", "configs",
                                 config["server_yaml"]),
                    os.path.join(root, "configs"))
    for mix in {w["traffic"] for w in bench["workloads"]}:
        with open(os.path.join(BENCH_DIR, "traffic", mix + ".json")) as f:
            params = json.load(f)
        params.update(over["traffic"].get(mix, {}))
        with open(os.path.join(root, "traffic", mix + ".json"), "w") as f:
            json.dump(params, f, indent=1)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return root


@pytest.fixture(scope="session")
def rehearsal_root(tmp_path_factory) -> str:
    return build_rehearsal(str(tmp_path_factory.mktemp("rehearsal")))
