"""Deterministic generator for tests/fixtures/whatif_mix — the 50-job
recorded tenant mix behind the what-if simulator's unit matrix and the
CI no-deps smoke.

The mix is engineered so each counterfactual axis has a measurable
signal:

* pool 2 slices x 4 hosts, quotas ``capped=2``;
* tenant ``capped`` submits steady 1-host jobs — at quota 2 the third
  concurrent job ALWAYS quota-holds, so ``--quota capped=4`` strictly
  reduces the tenant's queue-wait p99 (asserted in CI);
* tenant ``batch`` runs elastic 3-host gangs (min_hosts=1) — the
  preemption victims;
* tenant ``search`` runs priority-5 2-host gangs — mid-queue pressure;
* two priority-10 ``urgent`` 6-host gangs land mid-trace and force
  elastic shrinks, so ``--set tony.fleet.sim-preemption=false`` has
  victims to un-preempt.

Everything is integer arithmetic from a fixed time origin — re-running
the script reproduces the checked-in journal byte for byte (test-
enforced), which is what lets the fixture be regenerated instead of
hand-edited.

Usage: python tests/scripts/gen_whatif_mix.py [OUT_JOURNAL]
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from tony_tpu.fleet import simulator as fsim  # noqa: E402

#: fixed sim-time origin (2020-09-13T12:26:40Z) — journal timestamps
#: are sim-time, never wall-clock, so output is reproducible.
ORIGIN_MS = 1_600_000_000_000

OUT = os.path.join(REPO, "tests", "fixtures", "whatif_mix",
                   "fleet.journal.jsonl")


def build_workload() -> fsim.Workload:
    jobs = []
    submit = ORIGIN_MS
    for i in range(1, 51):
        job_id = f"wf-{i:04d}"
        # deterministic pseudo-jitter: spread submits 2-8 s apart and
        # vary work +/-30% so queue dynamics are not metronomic
        submit += 2_000 + (i * 7919) % 6_000
        jitter = ((i * 104729) % 600) or 300
        if i in (18, 36):
            tenant, priority = "urgent", 10
            hosts, min_hosts = 6, 0
            work = hosts * 45_000
        elif i % 5 == 0:
            # long 1-host jobs under quota 2: the third concurrent one
            # quota-holds while the pool still has free hosts, so the
            # quota — not capacity — is the binding constraint
            tenant, priority = "capped", 0
            hosts, min_hosts = 1, 0
            work = 90_000 + jitter * 100
        elif i % 5 in (1, 2):
            tenant, priority = "search", 5
            hosts, min_hosts = 2, 1
            work = hosts * (18_000 + jitter * 20)
        else:
            tenant, priority = "batch", 0
            hosts, min_hosts = 3, 1
            work = hosts * (26_000 + jitter * 30)
        jobs.append(fsim.SimJob(
            job_id=job_id, tenant=tenant, priority=priority,
            hosts=hosts, min_hosts=min_hosts, model=f"m-{tenant}",
            seq=i, submit_ms=submit, work_chip_ms=work,
            recorded_state="FINISHED"))
    return fsim.Workload(slices=2, hosts_per_slice=4,
                         quotas={"capped": 2}, jobs=jobs)


def main() -> int:
    out = sys.argv[1] if len(sys.argv) > 1 else OUT
    os.makedirs(os.path.dirname(out), exist_ok=True)
    if os.path.exists(out):
        os.unlink(out)
    wl = build_workload()
    result = fsim.simulate(wl, recorder=fsim.JournalRecorder(out))
    m = result["metrics"]
    print(f"wrote {out}")
    print(f"  jobs={m['jobs']} granted={m['granted']} "
          f"preemptions={m['preemptions']} restores={m['restores']} "
          f"makespan_s={m['makespan_s']}")
    print(f"  queue_wait_p99_s={m['queue_wait_p99_s']} "
          f"quota_hold_s={m['quota_hold_s']} "
          f"capacity_hold_s={m['capacity_hold_s']}")
    capped = result["per_tenant"].get("capped") or {}
    print(f"  capped: p99={capped.get('queue_wait_p99_s')} "
          f"holds={capped.get('holds_s')}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
