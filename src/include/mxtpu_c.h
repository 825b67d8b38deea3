/* Flat C ABI for the mxnet_tpu runtime.
 *
 * Role parity: reference `include/mxnet/c_api.h` + `c_predict_api.h` — the
 * single C boundary every language binding crosses (SURVEY §2.3). The
 * groups below mirror the reference's: NDArray CRUD (c_api.cc), imperative
 * invoke (c_api_ndarray.cc), autograd (c_api_ndarray.cc), symbol
 * (c_api_symbolic.cc), executor (c_api_executor.cc), kvstore
 * (c_api.cc:986-1331), data iterators (c_api.cc), RecordIO (c_api.cc),
 * inference predictor (c_predict_api.cc), runtime info (libinfo).
 *
 * Deviations from the reference ABI (deliberate, documented):
 *   - shapes are int64_t (the reference carries both uint32 and 64-bit
 *     variants of every shape call; one 64-bit form replaces each pair);
 *   - dtypes are strings ("float32") not enum ints;
 *   - devices are strings ("cpu", "tpu(0)") not (dev_type, dev_id) pairs;
 *   - operator params cross as JSON (MXImperativeInvoke) or string
 *     key/value arrays (symbol/iter creation), matching the reference's
 *     const char** keys/vals convention;
 *   - no separate "Ex"/"64" variants.
 *
 * Conventions (same as the reference ABI):
 *   - every function returns 0 on success, -1 on failure;
 *   - on failure MXGetLastError() returns a human-readable message;
 *   - handles are opaque; release NDArrays with MXNDArrayFree and every
 *     other handle with its matching *Free;
 *   - returned pointer arrays (names, shapes, handles) live in
 *     thread-local storage owned by the library and stay valid until the
 *     next ABI call on the same thread — copy out before calling again.
 */
#ifndef MXTPU_C_H_
#define MXTPU_C_H_

#include <stddef.h>
#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

typedef void* NDArrayHandle;
typedef void* SymbolHandle;
typedef void* ExecutorHandle;
typedef void* KVStoreHandle;
typedef void* DataIterHandle;
typedef void* RecordIOHandle;
typedef void* PredictorHandle;
typedef void* AtomicSymbolCreator;
typedef void* CachedOpHandle;
/* monitor callback: (output name, array, closure) */
typedef void (*ExecutorMonitorCallback)(const char*, NDArrayHandle, void*);
/* store-side updater: (key, aggregated recv, stored local, closure) */
typedef void (*MXKVStoreUpdater)(int, NDArrayHandle, NDArrayHandle, void*);
typedef void (*MXKVStoreStrUpdater)(const char*, NDArrayHandle,
                                    NDArrayHandle, void*);

/* ------------------------------------------------------------ lifecycle */

/* Boot/attach the runtime. extra_sys_path: directory containing the
 * mxnet_tpu package (NULL if already importable). Safe to call from a
 * process that already hosts a Python interpreter. */
int MXTpuInit(const char* extra_sys_path);

const char* MXGetLastError(void);

/* version as 10000*major + 100*minor + patch (reference MXNET_VERSION) */
int MXGetVersion(int* out);

/* graceful teardown (reference MXNotifyShutdown): waits for outstanding
 * work; where MXTpuInit booted the interpreter it is finalized too, so a
 * standalone host calls this last, before it returns from main(), and
 * no entry point after it */
int MXNotifyShutdown(void);

int MXRandomSeed(int seed);
int MXSetNumOMPThreads(int num);
/* number of accelerator devices visible to the runtime */
int MXGetGPUCount(int* out);
/* build/runtime feature flags (reference MXLibInfoFeatures) */
int MXLibInfoFeatures(const char*** out_names, const int** out_enabled,
                      int* out_size);
int MXIsNumpyShape(int* out);
int MXSetIsNumpyShape(int is_np_shape, int* prev);

/* -------------------------------------------------------------- ndarray */

int MXNDArrayCreate(const int64_t* shape, int ndim, const char* dtype,
                    NDArrayHandle* out);
/* ctx: "cpu", "cpu(0)", "tpu(0)" (NULL = current context) */
int MXNDArrayCreateEx(const int64_t* shape, int ndim, const char* dtype,
                      const char* ctx, NDArrayHandle* out);
int MXNDArrayFree(NDArrayHandle handle);
int MXNDArrayGetShape(NDArrayHandle handle, int* out_ndim,
                      int64_t* out_shape, int max_ndim);
/* dtype name, e.g. "float32" (thread-local storage) */
int MXNDArrayGetDType(NDArrayHandle handle, const char** out);
/* device string, e.g. "tpu(0)" (thread-local storage) */
int MXNDArrayGetContext(NDArrayHandle handle, const char** out);
/* "default" | "row_sparse" | "csr" (thread-local storage) */
int MXNDArrayGetStorageType(NDArrayHandle handle, const char** out);
int MXNDArrayReshape(NDArrayHandle handle, int ndim, const int64_t* dims,
                     NDArrayHandle* out);
int MXNDArraySlice(NDArrayHandle handle, int64_t begin, int64_t end,
                   NDArrayHandle* out);
int MXNDArrayAt(NDArrayHandle handle, int64_t idx, NDArrayHandle* out);
int MXNDArrayDetach(NDArrayHandle handle, NDArrayHandle* out);
/* gradient buffer attached by MXAutogradMarkVariables (new handle) */
int MXNDArrayGetGrad(NDArrayHandle handle, NDArrayHandle* out);
int MXNDArraySyncCopyFromCPU(NDArrayHandle handle, const float* data,
                             int64_t size);
int MXNDArraySyncCopyToCPU(NDArrayHandle handle, float* data, int64_t size);
int MXNDArrayWaitToRead(NDArrayHandle handle);
int MXNDArrayWaitAll(void);
/* Save arrays to the reference .params container. keys may be NULL (saves
 * a list). */
int MXNDArraySave(const char* fname, int num_args, NDArrayHandle* args,
                  const char** keys);
/* Load a .params container. Names array is empty (size 0) when the file
 * holds an unnamed list. Handles are owned by the caller. */
int MXNDArrayLoad(const char* fname, int* out_size,
                  NDArrayHandle** out_arr, int* out_name_size,
                  const char*** out_names);

/* ------------------------------------------------------------ operators */

/* Invoke a registered operator by name; kwargs_json carries non-tensor
 * parameters as a JSON object (may be NULL). On entry *num_outputs is the
 * capacity of out_array; on success it holds the actual output count. */
int MXImperativeInvoke(const char* op_name, NDArrayHandle* inputs,
                       int num_inputs, const char* kwargs_json,
                       NDArrayHandle* out_array, int* num_outputs);

int MXListAllOpNames(int* out_size, const char*** out_array);

/* ------------------------------------------------------------- autograd */

int MXAutogradSetIsRecording(int is_recording, int* prev);
int MXAutogradSetIsTraining(int is_training, int* prev);
int MXAutogradIsRecording(int* out);
int MXAutogradIsTraining(int* out);
/* grad_reqs: 0=null 1=write 2=write-inplace 3=add (reference OpReqType) */
int MXAutogradMarkVariables(int num_var, NDArrayHandle* var_handles,
                            const int* grad_reqs,
                            NDArrayHandle* grad_handles);
/* ograd_handles may be NULL (implicit ones-like heads) */
int MXAutogradBackward(int num_output, NDArrayHandle* output_handles,
                       NDArrayHandle* ograd_handles, int retain_graph);

/* --------------------------------------------------------------- symbol */

int MXSymbolCreateVariable(const char* name, SymbolHandle* out);
/* Two-phase construction (reference c_api_symbolic.cc): create an atomic
 * node with its string params, then compose inputs into the SAME handle. */
int MXSymbolCreateAtomicSymbol(const char* op_name, int num_param,
                               const char** keys, const char** vals,
                               SymbolHandle* out);
/* keys[i] may be "" / NULL for positional composition */
int MXSymbolCompose(SymbolHandle sym, const char* name, int num_args,
                    const char** keys, SymbolHandle* args);
int MXSymbolCreateGroup(int num_symbols, SymbolHandle* symbols,
                        SymbolHandle* out);
int MXSymbolGetOutput(SymbolHandle sym, int index, SymbolHandle* out);
int MXSymbolGetInternals(SymbolHandle sym, SymbolHandle* out);
/* *out is NULL when the symbol is unnamed; thread-local storage */
int MXSymbolGetName(SymbolHandle sym, const char** out, int* success);
int MXSymbolGetNumOutputs(SymbolHandle sym, int* out);
int MXSymbolListArguments(SymbolHandle sym, int* out_size,
                          const char*** out_array);
int MXSymbolListOutputs(SymbolHandle sym, int* out_size,
                        const char*** out_array);
int MXSymbolListAuxiliaryStates(SymbolHandle sym, int* out_size,
                                const char*** out_array);
/* Provide shapes for num_args named arguments (flattened: arg i occupies
 * ndims[i] entries of shape_data starting at offsets[i]). Results come
 * back the same flattened way in thread-local storage; *complete is 1
 * when every argument shape was inferred. partial=1 tolerates unknowns
 * (reference MXSymbolInferShapePartial). */
int MXSymbolInferShape(SymbolHandle sym, int num_args, const char** keys,
                       const int* ndims, const int64_t* shape_data,
                       int partial,
                       int* in_size, const int** in_ndims,
                       const int64_t** in_data,
                       int* out_size, const int** out_ndims,
                       const int64_t** out_data,
                       int* aux_size, const int** aux_ndims,
                       const int64_t** aux_data,
                       int* complete);
/* JSON string in thread-local storage */
int MXSymbolSaveToJSON(SymbolHandle sym, const char** out_json);
int MXSymbolCreateFromJSON(const char* json, SymbolHandle* out);
int MXSymbolSaveToFile(SymbolHandle sym, const char* fname);
int MXSymbolCreateFromFile(const char* fname, SymbolHandle* out);
int MXSymbolCopy(SymbolHandle sym, SymbolHandle* out);
int MXSymbolGetAttr(SymbolHandle sym, const char* key, const char** out,
                    int* success);
int MXSymbolSetAttr(SymbolHandle sym, const char* key, const char* value);
/* human-readable graph dump (reference MXSymbolPrint) */
int MXSymbolPrint(SymbolHandle sym, const char** out);
int MXSymbolFree(SymbolHandle sym);

/* ------------------------------------------------------------- executor */

/* Allocate arg/grad/aux arrays from inferred shapes and return a bound
 * executor (reference MXExecutorSimpleBind). Provide the data-variable
 * shapes the same flattened way as MXSymbolInferShape. grad_req: "write"
 * | "add" | "null". */
int MXExecutorSimpleBind(SymbolHandle sym, const char* ctx,
                         const char* grad_req, int num_provided,
                         const char** keys, const int* ndims,
                         const int64_t* shape_data, ExecutorHandle* out);
int MXExecutorForward(ExecutorHandle exec, int is_train);
/* ograd_handles may be NULL for default head gradients */
int MXExecutorBackward(ExecutorHandle exec, int num_ograds,
                       NDArrayHandle* ograd_handles);
/* Output/arg/grad/aux arrays: new NDArray handles (caller frees each),
 * pointer array in thread-local storage. Grad entries may be NULL when
 * grad_req was "null" for that argument. */
int MXExecutorOutputs(ExecutorHandle exec, int* out_size,
                      NDArrayHandle** out);
int MXExecutorArgArrays(ExecutorHandle exec, int* out_size,
                        NDArrayHandle** out);
int MXExecutorGradArrays(ExecutorHandle exec, int* out_size,
                         NDArrayHandle** out);
int MXExecutorAuxArrays(ExecutorHandle exec, int* out_size,
                        NDArrayHandle** out);
/* argument names, same order as Arg/GradArrays */
int MXExecutorArgNames(ExecutorHandle exec, int* out_size,
                       const char*** out_array);
int MXExecutorPrint(ExecutorHandle exec, const char** out);
int MXExecutorFree(ExecutorHandle exec);

/* -------------------------------------------------------------- kvstore */

/* type: "local" | "device" | "dist_sync" ... (reference MXKVStoreCreate) */
int MXKVStoreCreate(const char* type, KVStoreHandle* out);
int MXKVStoreInit(KVStoreHandle kv, int num, const char** keys,
                  NDArrayHandle* vals);
/* repeated keys aggregate their values (reference per-device push) */
int MXKVStorePush(KVStoreHandle kv, int num, const char** keys,
                  NDArrayHandle* vals, int priority);
int MXKVStorePull(KVStoreHandle kv, int num, const char** keys,
                  NDArrayHandle* outs, int priority);
int MXKVStoreGetType(KVStoreHandle kv, const char** out);
int MXKVStoreGetRank(KVStoreHandle kv, int* out);
int MXKVStoreGetGroupSize(KVStoreHandle kv, int* out);
int MXKVStoreBarrier(KVStoreHandle kv);
int MXKVStoreGetNumDeadNode(KVStoreHandle kv, int node_id, int* out);
int MXKVStoreSetGradientCompression(KVStoreHandle kv, int num_params,
                                    const char** keys, const char** vals);
int MXKVStoreFree(KVStoreHandle kv);

/* --------------------------------------------------------------- dataio */

int MXListDataIters(int* out_size, const char*** out_array);
/* name from MXListDataIters; params as string key/value pairs, e.g.
 * {"data_csv": "/x.csv", "data_shape": "(4,)", "batch_size": "32"} */
int MXDataIterCreateIter(const char* name, int num_param,
                         const char** keys, const char** vals,
                         DataIterHandle* out);
/* *out = 1 when a batch is available, 0 at end of data */
int MXDataIterNext(DataIterHandle iter, int* out);
int MXDataIterBeforeFirst(DataIterHandle iter);
/* new handles onto the CURRENT batch (caller frees) */
int MXDataIterGetData(DataIterHandle iter, NDArrayHandle* out);
int MXDataIterGetLabel(DataIterHandle iter, NDArrayHandle* out);
int MXDataIterGetPadNum(DataIterHandle iter, int* out);
int MXDataIterFree(DataIterHandle iter);

/* ------------------------------------------------------------- recordio */

int MXRecordIOWriterCreate(const char* uri, RecordIOHandle* out);
int MXRecordIOWriterWriteRecord(RecordIOHandle handle, const char* buf,
                                int64_t size);
int MXRecordIOWriterTell(RecordIOHandle handle, int64_t* out);
int MXRecordIOWriterFree(RecordIOHandle handle);
int MXRecordIOReaderCreate(const char* uri, RecordIOHandle* out);
/* *out_size = -1 at end of file; record bytes live in thread-local
 * storage until the next read on this thread */
int MXRecordIOReaderReadRecord(RecordIOHandle handle, const char** out_buf,
                               int64_t* out_size);
int MXRecordIOReaderSeek(RecordIOHandle handle, int64_t pos);
int MXRecordIOReaderTell(RecordIOHandle handle, int64_t* out);
int MXRecordIOReaderFree(RecordIOHandle handle);

/* -------------------------------------------------------------- predict */

/* Inference-only executor over an exported model (reference
 * c_predict_api.cc). symbol_json: the -symbol.json content; param_bytes:
 * the .params file CONTENT (not a path); input shapes flattened as in
 * MXSymbolInferShape. */
int MXPredCreate(const char* symbol_json, const void* param_bytes,
                 int64_t param_size, const char* ctx, int num_input,
                 const char** input_keys, const int* input_ndims,
                 const int64_t* input_shape_data, PredictorHandle* out);
int MXPredSetInput(PredictorHandle pred, const char* name,
                   const float* data, int64_t size);
int MXPredForward(PredictorHandle pred);
int MXPredGetOutputShape(PredictorHandle pred, int index,
                         const int64_t** out_shape, int* out_ndim);
int MXPredGetOutput(PredictorHandle pred, int index, float* data,
                    int64_t size);
/* re-bind with new input shapes (reference MXPredReshape) */
int MXPredReshape(PredictorHandle pred, int num_input,
                  const char** input_keys, const int* input_ndims,
                  const int64_t* input_shape_data);
int MXPredFree(PredictorHandle pred);

/* ------------------------------------------------------------- profiler */

/* state: "run" | "stop" */
int MXSetProfilerState(const char* state);
int MXSetProfilerConfig(int num_params, const char** keys,
                        const char** vals);
int MXDumpProfile(int finished);

/* ---------------------------------------------------------------------
 * Round-5 surface: binding-codegen introspection (what makes new
 * language bindings mechanical, reference c_api.h:1076-1120), cached
 * ops, monitor/updater callbacks, Ex/64 variants (aliases: canonical
 * entries are already 64-bit/string-keyed, see preamble), profiler
 * tail. */

int MXSymbolListAtomicSymbolCreators(int* out_size,
                                     AtomicSymbolCreator** out_array);
int MXSymbolGetAtomicSymbolName(AtomicSymbolCreator creator,
                                const char** name);
int MXSymbolGetAtomicSymbolInfo(
    AtomicSymbolCreator creator, const char** name,
    const char** description, int* num_args, const char*** arg_names,
    const char*** arg_type_infos, const char*** arg_descriptions,
    const char** key_var_num_args, const char** return_type);

int MXSymbolInferType(SymbolHandle sym, int num_args, const char** keys,
                      const char** types, int partial, int* in_size,
                      const char*** in_types, int* out_size,
                      const char*** out_types, int* aux_size,
                      const char*** aux_types, int* complete);
int MXSymbolInferTypePartial(SymbolHandle sym, int num_args,
                             const char** keys, const char** types,
                             int* in_size, const char*** in_types,
                             int* out_size, const char*** out_types,
                             int* aux_size, const char*** aux_types,
                             int* complete);
int MXSymbolGetChildren(SymbolHandle sym, SymbolHandle* out);
int MXSymbolRemoveAmpCast(SymbolHandle sym, SymbolHandle* out);
int MXSymbolInferShapeEx(SymbolHandle sym, int num_args, const char** keys,
                         const int* ndims, const int64_t* shape_data,
                         int partial, int* in_size, const int** in_ndims,
                         const int64_t** in_data, int* out_size,
                         const int** out_ndims, const int64_t** out_data,
                         int* aux_size, const int** aux_ndims,
                         const int64_t** aux_data, int* complete);
int MXSymbolInferShape64(SymbolHandle sym, int num_args, const char** keys,
                         const int* ndims, const int64_t* shape_data,
                         int partial, int* in_size, const int** in_ndims,
                         const int64_t** in_data, int* out_size,
                         const int** out_ndims, const int64_t** out_data,
                         int* aux_size, const int** aux_ndims,
                         const int64_t** aux_data, int* complete);
int MXSymbolInferShapePartial(
    SymbolHandle sym, int num_args, const char** keys, const int* ndims,
    const int64_t* shape_data, int* in_size, const int** in_ndims,
    const int64_t** in_data, int* out_size, const int** out_ndims,
    const int64_t** out_data, int* aux_size, const int** aux_ndims,
    const int64_t** aux_data, int* complete);
int MXSymbolInferShapePartial64(
    SymbolHandle sym, int num_args, const char** keys, const int* ndims,
    const int64_t* shape_data, int* in_size, const int** in_ndims,
    const int64_t** in_data, int* out_size, const int** out_ndims,
    const int64_t** out_data, int* aux_size, const int** aux_ndims,
    const int64_t** aux_data, int* complete);

int MXExecutorSetMonitorCallback(ExecutorHandle exec,
                                 ExecutorMonitorCallback cb, void* cb_data);
int MXExecutorSetMonitorCallbackEX(ExecutorHandle exec,
                                   ExecutorMonitorCallback cb,
                                   void* cb_data, int monitor_all);
int MXExecutorReshape(int partial_shaping, int allow_up_sizing,
                      const char* ctx, int num_provided, const char** keys,
                      const int* ndims, const int64_t* shape_data,
                      ExecutorHandle shared_exec, ExecutorHandle* out);
int MXExecutorReshapeEx(int partial_shaping, int allow_up_sizing,
                        const char* ctx, int num_provided,
                        const char** keys, const int* ndims,
                        const int64_t* shape_data,
                        ExecutorHandle shared_exec, ExecutorHandle* out);
int MXExecutorGetOptimizedSymbol(ExecutorHandle exec, SymbolHandle* out);
int MXExecutorSimpleBindEx(SymbolHandle sym, const char* ctx,
                           const char* grad_req, int num_provided,
                           const char** keys, const int* ndims,
                           const int64_t* shape_data, ExecutorHandle* out);
int MXExecutorSimpleBindEx64(SymbolHandle sym, const char* ctx,
                             const char* grad_req, int num_provided,
                             const char** keys, const int* ndims,
                             const int64_t* shape_data,
                             ExecutorHandle* out);

/* cached op: inputs ordered as list_arguments() + list_auxiliary_states() */
int MXCreateCachedOp(SymbolHandle sym, CachedOpHandle* out);
int MXCreateCachedOpEx(SymbolHandle sym, int num_flags, const char** keys,
                       const char** vals, CachedOpHandle* out);
int MXInvokeCachedOp(CachedOpHandle handle, int num_inputs,
                     NDArrayHandle* inputs, int* num_outputs,
                     NDArrayHandle** outputs);
int MXInvokeCachedOpEx(CachedOpHandle handle, int num_inputs,
                       NDArrayHandle* inputs, int* num_outputs,
                       NDArrayHandle** outputs, const int** out_stypes);
int MXFreeCachedOp(CachedOpHandle handle);

int MXAutogradBackwardEx(int num_output, NDArrayHandle* output_handles,
                         NDArrayHandle* ograd_handles, int num_variables,
                         NDArrayHandle* var_handles, int retain_graph,
                         int create_graph, int is_train,
                         NDArrayHandle** grad_handles, int** grad_stypes);

int MXKVStoreIsWorkerNode(int* out);
int MXKVStoreIsServerNode(int* out);
int MXKVStoreIsSchedulerNode(int* out);
int MXKVStoreSetBarrierBeforeExit(KVStoreHandle kv, int do_barrier);
int MXKVStoreRunServer(KVStoreHandle kv, void* controller, void* cb_data);
int MXKVStoreSendCommmandToServers(KVStoreHandle kv, int head,
                                   const char* body);
int MXKVStoreSetUpdater(KVStoreHandle kv, MXKVStoreUpdater cb,
                        void* cb_data);
int MXKVStoreSetUpdaterEx(KVStoreHandle kv, MXKVStoreUpdater cb,
                          MXKVStoreStrUpdater str_cb, void* cb_data);
int MXKVStorePushPull(KVStoreHandle kv, int num, const char** keys,
                      NDArrayHandle* ins, NDArrayHandle* outs,
                      int priority);
int MXKVStorePushPullEx(KVStoreHandle kv, int num, const char** keys,
                        NDArrayHandle* ins, NDArrayHandle* outs,
                        int priority);
int MXKVStorePullRowSparse(KVStoreHandle kv, int num, const char** keys,
                           NDArrayHandle* outs, NDArrayHandle* row_ids,
                           int priority);
int MXKVStorePullRowSparseEx(KVStoreHandle kv, int num, const char** keys,
                             NDArrayHandle* outs, NDArrayHandle* row_ids,
                             int priority);
int MXKVStoreInitEx(KVStoreHandle kv, int num, const char** keys,
                    NDArrayHandle* vals);
int MXKVStorePushEx(KVStoreHandle kv, int num, const char** keys,
                    NDArrayHandle* vals, int priority);
int MXKVStorePullEx(KVStoreHandle kv, int num, const char** keys,
                    NDArrayHandle* outs, int priority);

int MXNDArrayCreateNone(NDArrayHandle* out);
int MXNDArrayWaitToWrite(NDArrayHandle handle);
int MXNDArraySaveRawBytes(NDArrayHandle handle, size_t* out_size,
                          const char** out_buf);
int MXNDArrayLoadFromRawBytes(const void* buf, size_t size,
                              NDArrayHandle* out);
int MXNDArrayLoadFromBuffer(const void* buf, size_t size, int* out_size,
                            NDArrayHandle** out, int* out_name_size,
                            const char*** out_names);
int MXNDArraySyncCopyFromNDArray(NDArrayHandle dst, NDArrayHandle src,
                                 int i);
int MXNDArrayGetGradState(NDArrayHandle handle, int* out);
int MXNDArraySetGradState(NDArrayHandle handle, int state);
int MXShallowCopyNDArray(NDArrayHandle src, NDArrayHandle* out);
int MXShallowCopySymbol(SymbolHandle src, SymbolHandle* out);
int MXNDArrayGetShapeEx(NDArrayHandle handle, int* out_ndim,
                        int64_t* out_shape, int max_ndim);
int MXNDArrayGetShape64(NDArrayHandle handle, int* out_ndim,
                        int64_t* out_shape, int max_ndim);
int MXNDArrayGetShapeEx64(NDArrayHandle handle, int* out_ndim,
                          int64_t* out_shape, int max_ndim);
int MXNDArrayReshape64(NDArrayHandle handle, int ndim, const int64_t* dims,
                       int reverse, NDArrayHandle* out);
int MXNDArraySlice64(NDArrayHandle handle, int64_t begin, int64_t end,
                     NDArrayHandle* out);
int MXNDArrayAt64(NDArrayHandle handle, int64_t idx, NDArrayHandle* out);
int MXNDArrayCreateEx64(const int64_t* shape, int ndim, const char* dtype,
                        const char* ctx, int delay_alloc,
                        NDArrayHandle* out);
int MXImperativeInvokeEx(const char* op_name, NDArrayHandle* inputs,
                         int num_inputs, const char* kwargs_json,
                         NDArrayHandle* out_array, int* num_outputs,
                         const int** out_stypes);

int MXStorageEmptyCache(const char* ctx);
int MXEngineSetBulkSize(int bulk_size, int* prev_bulk_size);
int MXRandomSeedContext(int seed, const char* ctx);
int MXLoadLib(const char* path, unsigned verbose);
int MXProfilePause(int paused);
int MXProcessProfilePause(int paused, int profile_process);
int MXSetProcessProfilerState(int state, int profile_process);
int MXSetProcessProfilerConfig(int num_params, const char** keys,
                               const char** vals, KVStoreHandle kv);
int MXDumpProcessProfile(int finished, int profile_process,
                         KVStoreHandle kv);
int MXAggregateProfileStatsPrint(const char** out_str, int reset);
int MXAggregateProfileStatsPrintEx(const char** out_str, int reset,
                                   int format, int sort_by, int ascending);
int MXGenBackendSubgraph(SymbolHandle sym, const char* backend,
                         SymbolHandle* out);
int MXOptimizeForBackend(SymbolHandle sym, const char* backend,
                         SymbolHandle* out);
int MXDataIterGetIterInfo(const char* iter_name, const char** name,
                          const char** description, int* num_args,
                          const char*** arg_names,
                          const char*** arg_type_infos,
                          const char*** arg_descriptions);

#ifdef __cplusplus
}
#endif

#endif  /* MXTPU_C_H_ */
