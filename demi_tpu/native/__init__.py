from .analysis import (
    ScanBuffers,
    analysis_native_available,
    digest_keys,
    prefixed_digests,
    prescription_digest,
    prescription_digests,
    racing_pair_scan,
    racing_prescriptions_batch,
    scan_backend,
)
from .codec import (
    native_available,
    pack_records,
    unpack_records,
    read_record_log,
    write_record_log,
)

__all__ = [
    "ScanBuffers",
    "analysis_native_available",
    "native_available",
    "pack_records",
    "unpack_records",
    "read_record_log",
    "write_record_log",
    "racing_pair_scan",
    "racing_prescriptions_batch",
    "prescription_digests",
    "prefixed_digests",
    "prescription_digest",
    "digest_keys",
    "scan_backend",
]
