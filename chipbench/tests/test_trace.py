"""The reduction from a profiler trace to device numbers, and the least
bytes of a block: on a small XSpace written out by hand (every number
known), and on a small trace recorded on one TPU v5e."""
import gzip
import os

import numpy as np
import pytest

from chipbench import cost
from chipbench import trace as tr
from chipbench.kinds import frequency

# device: ops [1,3) [2,4) [6,7) us inside a window [0,10) us; programs
# ingest [1,4) and [6,7), query [6,7); host spans tick [0,5), wait [5,8)
XSPACE = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 12000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 12000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit_ingest(3)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_query_many_tenant(9)" } }
  event_metadata { key: 3 value { id: 3 name: "sort.1" } }
  event_metadata { key: 4 value { id: 4 name: "fusion.2" } }
}
planes { id: 3 name: "/device:CUSTOM:Megascale Trace"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "not a chip" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 3000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "chipbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "chipbench.tick" } }
  event_metadata { key: 3 value { id: 3 name: "chipbench.wait" } }
}
'''
# three ticks of a 256-tenant service, traced on one TPU v5e with the
# benchmark's profiler options (``run.profile_options``), gzipped
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "small.xplane.pb.gz")


def test_summary_of_a_written_trace():
    from jax.profiler import ProfileData

    s = tr.summarize(ProfileData.from_text_proto(XSPACE))
    assert s.devices == 1
    assert s.window_s == pytest.approx(10e-6)
    assert s.busy_s == pytest.approx(4e-6)         # [1,4) and [6,7)
    assert s.programs["ingest"] == (pytest.approx(4e-6), 2)
    assert s.programs["query_many_tenant"] == (pytest.approx(1e-6), 1)
    assert s.program_s(["ingest", "query_many_tenant"])[1] == 3
    assert dict(s.top_ops) == {"ingest:sort.1": pytest.approx(2e-6),
                               "ingest:fusion.2": pytest.approx(2e-6),
                               "query_many_tenant:sort.1":
                               pytest.approx(1e-6)}
    # idle 6 us: [0,1) and [4,5) in the tick, [5,6) and [7,8) waiting,
    # [8,10) outside any span
    assert dict(s.idle_by_host) == {"chipbench.tick": pytest.approx(2e-6),
                                    "chipbench.wait": pytest.approx(2e-6),
                                    "driver": pytest.approx(2e-6)}


def test_program_and_op_names():
    assert tr.program_name("jit_ingest(12)") == "ingest"
    assert tr.program_name("jit__ready_token(3)") == "_ready_token"
    assert tr.program_name("topk_tenants") == "topk_tenants"
    assert tr.op_name("%while.3 = (s32[]) while(%t), body=%b") == "while.3"


def test_summary_of_a_recorded_trace():
    from jax.profiler import ProfileData

    with gzip.open(RECORDED, "rb") as f:
        s = tr.summarize(ProfileData.from_serialized_xspace(f.read()))
    assert s.devices == 1
    assert 0 < s.busy_s < s.window_s
    assert s.programs["ingest"][1] == 3              # three ticks' blocks
    assert s.programs["query_many_tenant"][1] == 3
    # a brute-force sweep over the XLA Ops intervals gives 5,280,955 ns
    assert s.busy_s == pytest.approx(5.280955e-3)
    idle = sum(v for _, v in s.idle_by_host)
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    assert "chipbench.tick" in dict(s.idle_by_host)


def test_trace_without_a_device_is_refused():
    from jax.profiler import ProfileData

    host_only = XSPACE[XSPACE.index("planes { id: 3"):]
    with pytest.raises(ValueError):
        tr.summarize(ProfileData.from_text_proto(host_only))


def test_block_least_bytes():
    bits, k = 16, 2000                     # k pads to 2048 lanes
    keys = np.array([(3 << bits) | 5, (3 << bits) | 9, (7 << bits) | 1,
                     (9 << bits) | 2, (11 << bits) | 4], np.int32)
    w = np.array([1, -1, 2, 0, 1], np.int32)     # tenant 9 only pads
    assert frequency.rows_reached(keys, w, bits) == 3
    assert frequency.block_least_bytes(keys, w, bits, k) == \
        3 * 2048 * 3 * 4 * 2 + 5 * 8


def test_peaks_are_published_and_unknown_kinds_refused():
    assert cost.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cost.peaks("cpu")
