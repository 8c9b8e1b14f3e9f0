"""Whether the timed path's answers are correct: the window's verdicts
against the plain reference (`refbls`), once the window has closed.

Every verdict is due in the window.  The check takes a sample drawn from
the seed, plus every round the program flagged and every planted corrupt
round whose verdict came in the window, plus every round a scan report
flagged.  The reference judges each from the stored rows by the chain's
own rule (signature of round r over r's message, chained on the stored
signature of r-1), not from the bytes the scanner handed over.

Compared numbers, each with the limit 0 (an exact comparison):
  verdict_mismatch  sampled rounds whose verdict differs from the reference
  report_mismatch   rounds the scan reports flag and the reference does not,
                    or the reference finds invalid and the report misses
  unanswered        rounds submitted in the window that got no verdict
  anchor_mismatch   published answers (`reference/anchors.json`) the
                    reference gets wrong: the reference's own check
  fell_back         failovers, watchdog trips and handles off the chip
                    (`cell.fell_back`)
"""

import json
import os
import random

from . import refbls

SAMPLE = 1024
LIMITS = {"verdict_mismatch": 0, "report_mismatch": 0, "unanswered": 0}
ANCHORS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "reference", "anchors.json")


def anchor_mismatch(path: str = ANCHORS) -> int:
    """Published answers the reference gets wrong: the curve's generators,
    RFC 9380's hash-to-curve vectors, and beacons the League of Entropy
    signed, each of which has to verify, and not with its round moved by
    one.  Independent of the program: a wrong constant, map or rule in
    the reference (whose signatures make the store) shows here."""
    with open(path) as f:
        doc = json.load(f)
    one = (1).to_bytes(32, "big")
    wrong = sum(refbls.base_mul(g, one).hex() != want
                for g, want in doc["generators"].items() if g in ("G1", "G2"))
    for v in doc["hash_to_curve"]["vectors"]:
        got = refbls.hash_to_curve(v["group"], v["msg"].encode(),
                                   v["dst"].encode())
        wrong += got.hex() != "".join(v["point"])
    for b in doc["beacons"]["items"]:
        group, chained, dst = refbls.SCHEMES[b["scheme"]]
        pk, sig = bytes.fromhex(b["public_key"]), bytes.fromhex(b["signature"])
        prev = bytes.fromhex(b["previous_signature"]) \
            if b["previous_signature"] else None
        for r, want in ((b["round"], True), (b["round"] + 1, False)):
            msg = refbls.beacon_message(chained, r, prev)
            wrong += refbls.verify(group, pk, msg, dst, sig) != want
    return wrong


def compare(window, reports, fixture, seed: int):
    """-> (checks {name: (value, limit)}, rounds compared)."""
    entries = []                    # (scan, round, program verdict)
    for scan, rounds, _sigs, _prevs, ok in window.chunks:
        entries.extend((scan, r, v) for r, v in zip(rounds, ok))
    rng = random.Random(seed * 7919 + 17)
    pick = set(rng.sample(range(len(entries)), min(SAMPLE, len(entries))))
    pick |= {i for i, (_, r, v) in enumerate(entries)
             if not v or r in fixture.corrupt}
    flagged = {}                    # scan -> rounds its report flags
    for scan, (report, cut) in enumerate(reports):
        flagged[scan] = {r for r in report.faulty_rounds if r <= cut}
    want = sorted({entries[i][1] for i in pick}
                  | set().union(*flagged.values()))
    ref = dict(zip(want, fixture.chain.verify_many(
        (r, fixture.prev_of(r), fixture.sigs[r]) for r in want)))
    verdict_mismatch = sum(1 for i in pick
                           if entries[i][2] != ref[entries[i][1]])
    report_mismatch = 0
    for scan in flagged:
        seen = {entries[i][1] for i in pick if entries[i][0] == scan}
        invalid = {r for r in seen | flagged[scan] if not ref[r]}
        report_mismatch += len(flagged[scan] ^ invalid)
    checks = {"verdict_mismatch": verdict_mismatch,
              "report_mismatch": report_mismatch,
              "unanswered": window.submitted - len(entries)}
    return {k: (v, LIMITS[k]) for k, v in checks.items()}, len(want)
