"""Every seed's stream holds the same work."""
import json

from benchmark.manifest import Manifest
from benchmark.stream import JobStream, ProbeStream

SEEDS = (0, 7, 1000000007, 2**31 + 5)
MIXED = {"loop": "closed", "in_flight": 8,
         "mix": [{"share": 5, "count": 2}, {"share": 2, "count": 10},
                 {"share": 1, "count": 40}]}


def _config():
    m = Manifest()
    return m.config("binpack-10k")


def test_every_prefix_of_whole_blocks_holds_the_same_shapes_for_every_seed():
    config = _config()
    block = 8
    for blocks in (1, 3, 25):
        want = None
        for seed in SEEDS:
            stream = JobStream(config, MIXED, seed)
            counts = stream.shape_counts(blocks * block)
            placements = sum(stream.placements(i) for i in range(blocks * block))
            assert counts == {0: 5 * blocks, 1: 2 * blocks, 2: blocks}
            want = want or placements
            assert placements == want == blocks * (5 * 2 + 2 * 10 + 40)


def test_the_seed_orders_the_stream():
    config = _config()
    orders = {
        seed: tuple(JobStream(config, MIXED, seed).placements(i) for i in range(64))
        for seed in SEEDS
    }
    assert len(set(orders.values())) > 1
    again = tuple(JobStream(config, MIXED, 7).placements(i) for i in range(64))
    assert again == orders[7]


def test_shipped_mixes_hold_one_shape_so_every_window_is_the_same_work():
    m = Manifest()
    for w in m.doc["workloads"]:
        config, traffic = m.config(w["config"]), m.traffic(w["traffic"])
        count = config["job"]["task_groups"][0]["count"]
        for seed in SEEDS:
            stream = JobStream(config, traffic, seed)
            assert {stream.placements(i) for i in range(500)} == {count}


def test_body_and_payload_are_the_same_job():
    config = _config()
    stream = JobStream(config, MIXED, 11)
    for i in (0, 5, 13):
        sent = json.loads(stream.body(i))["Job"]
        assert sent == stream.payload(i)
        assert sent["id"] == stream.job_id(i)
        assert sent["task_groups"][0]["count"] == stream.placements(i)


def test_a_probe_is_a_pinned_count_1_copy_of_the_job():
    stream = JobStream(_config(), MIXED, 11)
    probes = ProbeStream(stream, ["n9", "n4"])
    probe = probes.payload(3, "probe2")
    assert probe["task_groups"][0]["count"] == 1 and probes.placements(3) == 1
    assert probe["id"] == "probe2-0000003"
    assert probe["constraints"][:-1] == stream.payload(0)["constraints"]
    assert probe["constraints"][-1] == {
        "ltarget": "${node.unique.name}", "rtarget": "n4", "operand": "=",
    }
    assert probes.payload(4)["constraints"][-1]["rtarget"] == "n9"
    assert json.loads(probes.body(3, "probe2"))["Job"] == probe
    # the stream's own jobs are left as they were
    assert stream.payload(0)["task_groups"][0]["count"] == stream.placements(0)
