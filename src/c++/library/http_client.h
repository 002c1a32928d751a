// HTTP/REST client for the KServe-v2 protocol with the binary-tensor
// extension.
//
// Re-design of the reference InferenceServerHttpClient
// (reference src/c++/library/http_client.h:106-650).  The reference rides
// libcurl easy/multi; this environment has no libcurl headers, so the
// transport is a POSIX-socket keep-alive connection pool with the same
// wire behavior: scatter-gather request bodies (JSON header + raw tensor
// sections, no copy of tensor data into the body), the
// Inference-Header-Content-Length framing, TCP_NODELAY, and an async path
// on a worker thread pool (role of the reference's curl-multi
// AsyncTransfer loop, http_client.cc:1883-1968).

#pragma once

#include <condition_variable>
#include <deque>
#include <mutex>
#include <thread>

#include "common.h"
#include "tjson.h"
#include "tls.h"

namespace tc {

class HttpConnectionPool;

// TLS settings for https:// server URLs (API parity with the reference's
// curl-backed struct, reference http_client.h:46-87; served here by the
// dlopen'd-OpenSSL transport in tls.h).  verify_peer/verify_host keep the
// curl numeric convention: 0 disables, the defaults (1/2) enable.
struct HttpSslOptions {
  enum CERTTYPE { CERT_PEM = 0, CERT_DER = 1 };
  enum KEYTYPE { KEY_PEM = 0, KEY_DER = 1 };
  long verify_peer = 1;
  long verify_host = 2;
  std::string ca_info;       // PEM roots; empty = system default paths
  CERTTYPE cert_type = CERT_PEM;  // only PEM is supported
  std::string cert;          // client certificate chain
  KEYTYPE key_type = KEY_PEM;     // only PEM is supported
  std::string key;           // client private key
};

//==============================================================================
class InferenceServerHttpClient : public InferenceServerClient {
 public:
  // server_url may carry an http:// or https:// scheme; https enables
  // TLS with ssl_options (reference http_client.h:152-157).
  static Error Create(
      std::unique_ptr<InferenceServerHttpClient>* client,
      const std::string& server_url, bool verbose = false,
      int concurrency = 4,
      const HttpSslOptions& ssl_options = HttpSslOptions());

  ~InferenceServerHttpClient();

  Error IsServerLive(bool* live);
  Error IsServerReady(bool* ready);
  Error IsModelReady(
      bool* ready, const std::string& model_name,
      const std::string& model_version = "");

  Error ServerMetadata(std::string* server_metadata);
  Error ModelMetadata(
      std::string* model_metadata, const std::string& model_name,
      const std::string& model_version = "");
  Error ModelConfig(
      std::string* model_config, const std::string& model_name,
      const std::string& model_version = "");

  Error ModelRepositoryIndex(std::string* repository_index);
  Error LoadModel(
      const std::string& model_name, const std::string& config = "");
  Error UnloadModel(const std::string& model_name);

  Error ModelInferenceStatistics(
      std::string* infer_stat, const std::string& model_name = "",
      const std::string& model_version = "");

  Error UpdateTraceSettings(
      std::string* response, const std::string& model_name = "",
      const std::map<std::string, std::vector<std::string>>& settings = {});
  Error GetTraceSettings(
      std::string* settings, const std::string& model_name = "");

  Error UpdateLogSettings(
      std::string* response, const std::string& settings_json);
  Error GetLogSettings(std::string* settings);

  Error RegisterSystemSharedMemory(
      const std::string& name, const std::string& key, size_t byte_size,
      size_t offset = 0);
  Error UnregisterSystemSharedMemory(const std::string& name = "");
  Error SystemSharedMemoryStatus(std::string* status);

  // XLA/TPU shared memory — generalization of the reference's CUDA verbs
  // (reference http_client.h:411-442): raw_handle is the base64 handle
  // from the xla shm utility library.
  Error RegisterXlaSharedMemory(
      const std::string& name, const std::string& raw_handle,
      size_t byte_size, int device_ordinal = 0);
  Error UnregisterXlaSharedMemory(const std::string& name = "");
  Error XlaSharedMemoryStatus(std::string* status);

  Error RegisterCudaSharedMemory(
      const std::string& name, const std::string& raw_handle,
      size_t byte_size, int device_id = 0);
  Error UnregisterCudaSharedMemory(const std::string& name = "");
  Error CudaSharedMemoryStatus(std::string* status);

  // Compression algorithms: "" (none), "gzip", "deflate" — request-side
  // body compression and response-side Accept-Encoding (reference
  // http_client.cc:563-580 CompressInput via libcurl; zlib here).
  Error Infer(
      InferResult** result, const InferOptions& options,
      const std::vector<InferInput*>& inputs,
      const std::vector<const InferRequestedOutput*>& outputs =
          std::vector<const InferRequestedOutput*>(),
      const std::string& request_compression_algorithm = "",
      const std::string& response_compression_algorithm = "");

  Error AsyncInfer(
      OnCompleteFn callback, const InferOptions& options,
      const std::vector<InferInput*>& inputs,
      const std::vector<const InferRequestedOutput*>& outputs =
          std::vector<const InferRequestedOutput*>(),
      const std::string& request_compression_algorithm = "",
      const std::string& response_compression_algorithm = "");

  // Build an inference request body without sending (reference
  // http_client.h:122-138). Returns body and the JSON header length.
  static Error GenerateRequestBody(
      std::vector<uint8_t>* request_body, size_t* header_length,
      const InferOptions& options, const std::vector<InferInput*>& inputs,
      const std::vector<const InferRequestedOutput*>& outputs);

  // Parse a raw response body into an InferResult.
  static Error ParseResponseBody(
      InferResult** result, const std::vector<uint8_t>& response_body,
      size_t header_length);

 private:
  InferenceServerHttpClient(
      const std::string& url, bool verbose, int concurrency,
      const HttpSslOptions& ssl_options);

  Error Get(
      const std::string& path, long* http_code, std::string* response);
  Error Post(
      const std::string& path, const std::string& body, long* http_code,
      std::string* response,
      const std::map<std::string, std::string>& headers = {});
  Error PostBinary(
      const std::string& path, const std::vector<uint8_t>& body,
      size_t header_length, long* http_code, std::string* response,
      size_t* response_header_length, uint64_t timeout_us,
      const std::string& extra_headers = "",
      std::string* response_content_encoding = nullptr);
  // The send half of Infer. AsyncInfer serializes the body on the
  // caller's thread (an InferInput carries a read cursor, and one input
  // may be shared by several in-flight requests); its workers only send.
  Error SendInfer(
      InferResult** result, const InferOptions& options,
      std::vector<uint8_t> body, size_t header_length, RequestTimers timer,
      const std::string& request_compression_algorithm,
      const std::string& response_compression_algorithm);

  std::string host_;
  int port_;
  std::unique_ptr<HttpConnectionPool> pool_;

  // async worker pool
  void AsyncWorker();
  std::vector<std::thread> workers_;
  std::deque<std::function<void()>> async_queue_;
  std::mutex async_mu_;
  std::condition_variable async_cv_;
};

}  // namespace tc
