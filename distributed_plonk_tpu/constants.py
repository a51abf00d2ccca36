"""BLS12-381 curve and field constants.

All values are standard, publicly specified BLS12-381 parameters (as used by
the reference's `ark-bls12-381` dependency, see /root/reference/Cargo.toml:31).
Derived quantities (Montgomery constants, roots of unity) are computed here
from first principles so nothing is copied from any implementation.

Runtime knob glossary (DPT_* environment variables)
---------------------------------------------------
The single source of truth for every environment knob the package reads,
enforced by analysis.lint ENV01: an undocumented `DPT_*` string literal
anywhere in the package is a lint failure. Format mirrors the OBS01 metric
glossary — indented lines, the knob name separated from its description by
two or more spaces; a trailing `*` documents a whole family.

Kernel dispatch and device tuning (backend/, parallel/):

    DPT_FIELD_MUL             field mont_mul kernel: auto|f32|u32|pallas
    DPT_PALLAS_MIN_LANES      min lanes before the pallas mul engages (2048)
    DPT_PALLAS_LANE_TILE      pallas mul lane-tile width (512)
    DPT_MUL_MXU               pallas mul: use the MXU matmul core (0)
    DPT_MUL_LAZY              pallas mul: lazy-carry accumulation (1)
    DPT_CURVE_ADD             curve add kernel: xla|pallas (xla)
    DPT_NTT_RADIX             NTT stage radix: 2|4 (4)
    DPT_NTT_BATCH             NTT batch width for *_many paths (8)
    DPT_R3_FUSE               fuse the round-3 quotient pipeline (1)
    DPT_R3_BITREV             consumer-side bit-reversal fusion (1)
    DPT_QUOT_SLICE            round-3 quotient eval slice length (2^20)
    DPT_STREAM_SYNC_EVERY     drain the dispatch queue every N FFTs (4)
    DPT_STREAM_SYNC_MIN_M     min domain before stream draining arms (2^23)
    DPT_RELEASE_TABLES_MIN    free circuit tables at/above this n (2^19)
    DPT_MSM_KERNEL            MSM bucket kernel: auto|xla|pallas (auto)
    DPT_MSM_C                 MSM window bits (7)
    DPT_MSM_BATCH             MSM scalar batch width (8)
    DPT_MSM_JOB_BATCH         MSM jobs folded per device dispatch (16)
    DPT_MSM_GROUP_MAX         max MSM group size (512)
    DPT_MSM_PLANE_MB          bucket-plane HBM budget in MB (1536)
    DPT_MSM_PALLAS_VMEM_MB    pallas MSM VMEM budget in MB
    DPT_MSM_CALL_ADDS         lane-add budget of one MSM device call (2^27;
                              per device on a mesh: 8e6)
    DPT_BUCKET_UPDATE         bucket update strategy: auto|onehot|put
    DPT_PLANE_PACK            packed bucket planes (1)
    DPT_FIXED_BASE_CHUNK      fixed-base table build chunk size
    DPT_MESH_MIN_LOCAL        min per-device rows before mesh sharding (1024)
    DPT_MESH_LEASE            lease mesh backends to the pool (0)
    DPT_PALLAS_INTERPRET      run Pallas kernels interpreted: tests only (0)
    DPT_JAX_CACHE_DIR         fleet worker's compile-cache dir (--store)
    DPT_JAX_TRACE             jax.profiler span annotations on hot paths

Proof service and autoscaling (service/):

    DPT_PIPELINE              round-pipelined multi-job proving (1)
    DPT_PIPELINE_DEPTH        max in-flight pipelined jobs (4)
    DPT_BATCH_PROVE           shape-batched proving (1)
    DPT_PLACE_SMALL_MAX       small-job placement cutoff, gates (2^14)
    DPT_PLACE_LARGE_MIN       large-job placement cutoff, gates (2^18)
    DPT_SELF_VERIFY           verify-before-serve: auto|0|1 (auto)
    DPT_SLO_STANDARD_S        standard-class SLO seconds
    DPT_TTL_*                 per-SLO-class job TTL seconds (DPT_TTL_<CLASS>_S)
    DPT_JOURNAL_FSYNC         fsync the job journal per append (1)
    DPT_JOURNAL_COMPACT_EVERY journal compaction cadence, appends (512)
    DPT_PEER_FETCH_TIMEOUT_MS peer artifact-fetch timeout (5000)
    DPT_AUTOSCALE             autoscaler arm: 0|dry|1 (0)
    DPT_AUTOSCALE_TICK_S      autoscaler control-loop period (2)
    DPT_AS_MIN_WORKERS        autoscaler floor (1)
    DPT_AS_MAX_WORKERS        autoscaler ceiling (8)
    DPT_AS_UP_QUEUE           queue-per-worker upscale threshold (2)
    DPT_AS_UP_TICKS           consecutive ticks before upscale (2)
    DPT_AS_DOWN_TICKS         consecutive idle ticks before downscale (5)
    DPT_AS_UP_COOLDOWN_S      cooldown after an upscale (10)
    DPT_AS_DOWN_COOLDOWN_S    cooldown after a downscale (30)
    DPT_AS_SHED_WATERMARK     queue fraction where batch-class sheds (0.9)

Fleet runtime, faults, integrity (runtime/):

    DPT_CALL_TIMEOUT_MS       per-RPC timeout (600000)
    DPT_RECONNECT_TRIES       dispatcher reconnect attempts (3)
    DPT_BACKOFF_BASE_MS       reconnect backoff base (50)
    DPT_BACKOFF_MAX_MS        reconnect backoff cap (2000)
    DPT_FFT_QUORUM            min workers for a sharded FFT (2)
    DPT_FFT_TASK_TTL          worker FFT task GC TTL seconds (600)
    DPT_FFT_DONE_TTL          completed-task retention seconds (60)
    DPT_FFT_TASK_CAP          max concurrent worker FFT tasks (64)
    DPT_FLEET_EVAL            distribute round-4 evaluation (1)
    DPT_BREAKER_K             failures to open a worker breaker (3)
    DPT_PROBE_BASE_MS         breaker half-open probe base (200)
    DPT_PROBE_MAX_MS          breaker half-open probe cap (5000)
    DPT_INTEGRITY             result-integrity plane arm (1)
    DPT_INTEGRITY_MSM_DUP     MSM duplicate-execution fraction (0.05)
    DPT_INTEGRITY_NTT_RATE    FFT spot-check sampling rate (1.0)
    DPT_INTEGRITY_SUBGROUP    subgroup-check returned points (1)
    DPT_INTEGRITY_REFEREE_MAX max referee recompute size (2048)
    DPT_JOIN_RETRY_S          membership JOIN retry period (30)
    DPT_JOIN_TIMEOUT_MS       membership JOIN timeout (10000)
    DPT_SUP_PROBE_MS          supervisor liveness probe period (500)
    DPT_SUP_PROBE_TIMEOUT_MS  supervisor probe timeout (3000)
    DPT_SUP_MISS_BUDGET       missed probes before respawn (3)
    DPT_SUP_STARTUP_GRACE_S   no-probe grace after spawn
    DPT_SUP_BACKOFF_BASE_MS   respawn backoff base (250)
    DPT_SUP_BACKOFF_MAX_MS    respawn backoff cap (10000)
    DPT_SUP_FLAP_CAP          respawns inside the window before retire (5)
    DPT_SUP_FLAP_WINDOW_S     flap-counting window (60)
    DPT_SUP_RETIRE_TIMEOUT_S  graceful retire drain timeout (20)
    DPT_WORKER_TRACE_CAP      per-worker retained trace spans (32)
    DPT_FAULTS                chaos fault-injection spec (off unset)

Observability, checkpoints, stores (obs/, store/, top-level):

    DPT_LOG_CAP               structured-log ring capacity (512)
    DPT_LOG_LEVEL             structured-log emit threshold (debug)
    DPT_LOG_DIR               mirror structured logs to JSONL files
    DPT_PROFILE_MS            default on-demand profile window (250)
    DPT_PROFILE_HZ            host stack-sampler frequency (100)
    DPT_FLEET_SCRAPE_S        fleet metrics scrape period (5)
    DPT_CKPT_FSYNC            fsync prover checkpoints (0)
    DPT_STORE_JAX_SWEEP_S     compile-cache upload sweep period (300)
    DPT_WARM_SYNC_PREFIXES    store prefixes pulled on warm rejoin
"""

# BLS parameter (the curve family is parameterised by z; z is negative).
# All moduli below are validated against this parameterisation at import time.
BLS_Z = -0xD201000000010000

# --- Scalar field Fr ---------------------------------------------------------
# r = order of the BLS12-381 G1/G2 subgroups (255 bits); r = z^4 - z^2 + 1
R_MOD = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
assert R_MOD == BLS_Z ** 4 - BLS_Z ** 2 + 1

# Multiplicative generator of Fr* (arkworks' `GENERATOR` for Fr is 7; it is a
# primitive root mod r). Used as the coset shift for coset-FFTs
# (reference: Fr::multiplicative_generator() at src/worker.rs:76).
FR_GENERATOR = 7

# two-adicity: r - 1 = 2^32 * FR_ODD
FR_TWO_ADICITY = 32
FR_ODD = (R_MOD - 1) >> FR_TWO_ADICITY
assert (R_MOD - 1) == FR_ODD << FR_TWO_ADICITY and FR_ODD % 2 == 1

# 2^32-th primitive root of unity in Fr
FR_ROOT_OF_UNITY = pow(FR_GENERATOR, FR_ODD, R_MOD)

# --- Base field Fq -----------------------------------------------------------
# q = characteristic of the base field (381 bits); q = (z-1)^2 * r / 3 + z
Q_MOD = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
assert Q_MOD == (BLS_Z - 1) ** 2 * R_MOD // 3 + BLS_Z

# --- Curve equations ---------------------------------------------------------
# G1: y^2 = x^3 + 4 over Fq
G1_B = 4
# G2: y^2 = x^3 + 4(1+u) over Fq2 = Fq[u]/(u^2+1)
G2_B = (4, 4)

# --- Standard generators -----------------------------------------------------
G1_GEN_X = 0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB
G1_GEN_Y = 0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1

G2_GEN_X = (
    0x024AA2B2F08F0A91260805272DC51051C6E47AD4FA403B02B4510B647AE3D1770BAC0326A805BBEFD48056C8C121BDB8,
    0x13E02B6052719F607DACD3A088274F65596BD0D09920B61AB5DA61BBDC7F5049334CF11213945D57E5AC7D055D042B7E,
)
G2_GEN_Y = (
    0x0CE5D527727D6E118CC9CDC6DA2E351AADFD9BAA8CBDD3A76D429A695160D12C923AC9CC3BACA289E193548608B82801,
    0x0606C4A02EA734CC32ACD2B02BC28B99CB3E287E85A763AF267492AB572E99AB3F370D275CEC1DA1AAA9075FF05F79BE,
)

# Absolute value of the BLS parameter (for ate-style Miller loops)
BLS_X = -BLS_Z
BLS_X_IS_NEG = True

# --- Limb layouts for device kernels ----------------------------------------
# TPU integer units have no 64-bit multiply; we use 16-bit limbs held in
# uint32 lanes so a limb product fits in 32 bits with headroom for lazy
# carry accumulation (see backend/limbs.py).
LIMB_BITS = 16
LIMB_MASK = (1 << LIMB_BITS) - 1
FR_LIMBS = 16  # 256 bits
FQ_LIMBS = 24  # 384 bits

# Montgomery radixes match arkworks' 64-bit-limb layout (R = 2^256 for Fr,
# R = 2^384 for Fq) so Montgomery-form values are bit-compatible.
FR_MONT_R = (1 << 256) % R_MOD
FR_MONT_R2 = (FR_MONT_R * FR_MONT_R) % R_MOD
FR_MONT_INV = (-pow(R_MOD, -1, 1 << 256)) % (1 << 256)  # -r^-1 mod 2^256
FR_MONT_INV16 = FR_MONT_INV & LIMB_MASK  # -r^-1 mod 2^16 (per-limb CIOS)

FQ_MONT_R = (1 << 384) % Q_MOD
FQ_MONT_R2 = (FQ_MONT_R * FQ_MONT_R) % Q_MOD
FQ_MONT_INV = (-pow(Q_MOD, -1, 1 << 384)) % (1 << 384)
FQ_MONT_INV16 = FQ_MONT_INV & LIMB_MASK
