/*
 * Standalone C prediction ABI over exported .mxtpu artifacts.
 *
 * Role parity: the reference's c_predict_api
 * (include/mxnet/c_predict_api.h:78-200 — MXPredCreate / SetInput /
 * Forward / GetOutputShape / GetOutput / Free, with the per-thread error
 * string of src/c_api/c_api_error.cc). TPU-native redesign of the
 * creation contract: instead of (symbol JSON + packed param bytes +
 * dev_type), a predictor is created from an .mxtpu artifact (StableHLO
 * bytecode + signature, written by mxnet_tpu.predict.export_model) and
 * any PJRT plugin .so — no framework runtime, no Python, no graph JSON.
 *
 * Conventions shared with the reference ABI:
 *   - every function returns 0 on success, -1 on failure;
 *   - MXTPUPredGetLastError() returns the failing call's message
 *     (thread-local, valid until the thread's next failing call);
 *   - shape pointers returned by GetInput/OutputShape stay valid until
 *     the next call on the same handle;
 *   - inputs are addressed by index in artifact signature order (the
 *     signature carries no tensor names — a feedforward artifact's
 *     single input is index 0, where the reference used key "data").
 */
#ifndef MXTPU_C_PREDICT_API_H_
#define MXTPU_C_PREDICT_API_H_

#include <stddef.h>
#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

typedef void* MXTPUPredictorHandle;

/* Thread-local message of this thread's most recent failing call. */
const char* MXTPUPredGetLastError(void);

/* Create a predictor from an artifact and a PJRT plugin.
 * opt_specs: num_opts strings in the CLI --opt grammar
 * ("name=int:N" | "name=str:S"), passed to PJRT_Client_Create as
 * NamedValues (some plugins require several; NULL/0 for none). */
int MXTPUPredCreate(const char* artifact_path,
                    const char* plugin_so,
                    const char* const* opt_specs,
                    int num_opts,
                    MXTPUPredictorHandle* out);

/* PJRT platform name of the backing client (e.g. "tpu"). The pointer is
 * owned by the handle and valid until MXTPUPredFree. */
int MXTPUPredGetPlatform(MXTPUPredictorHandle handle, const char** name);

int MXTPUPredGetInputCount(MXTPUPredictorHandle handle, int* count);
int MXTPUPredGetOutputCount(MXTPUPredictorHandle handle, int* count);

/* Shape/dtype of one input/output slot. dtype_name receives a static
 * string ("f32", "bf16", "s32", ...); pass NULL for fields you don't
 * need. shape_data stays valid until the next call on this handle. */
int MXTPUPredGetInputShape(MXTPUPredictorHandle handle, int index,
                           const int64_t** shape_data, int* ndim,
                           const char** dtype_name);
int MXTPUPredGetOutputShape(MXTPUPredictorHandle handle, int index,
                            const int64_t** shape_data, int* ndim,
                            const char** dtype_name);

/* Stage input `index` for the next forward. `size` counts f32 elements
 * (safety check against the signature, like the reference's
 * MXPredSetInput); the slot must be f32-typed. */
int MXTPUPredSetInput(MXTPUPredictorHandle handle, int index,
                      const float* data, uint64_t size);

/* Raw-bytes variant for non-f32 inputs: `nbytes` must equal the slot's
 * signature byte size. */
int MXTPUPredSetInputBytes(MXTPUPredictorHandle handle, int index,
                           const void* data, uint64_t nbytes);

/* Run one forward pass over the staged inputs (all slots must be set;
 * they stay staged for repeated Forward calls). */
int MXTPUPredForward(MXTPUPredictorHandle handle);

/* Copy output `index` of the last Forward. Element-count-checked f32
 * variant + raw-bytes variant, mirroring SetInput. */
int MXTPUPredGetOutput(MXTPUPredictorHandle handle, int index,
                       float* data, uint64_t size);
int MXTPUPredGetOutputBytes(MXTPUPredictorHandle handle, int index,
                            void* data, uint64_t nbytes);

int MXTPUPredFree(MXTPUPredictorHandle handle);

#ifdef __cplusplus
}
#endif

#endif /* MXTPU_C_PREDICT_API_H_ */
